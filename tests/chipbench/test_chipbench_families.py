"""The seam between the benchmark's shared code and a model family
(`chipbench/families/<family>.py`): the dense family draws and computes
what the benchmark did before it had families, a family added as files
only runs a whole cell, and a family with two stacks of different layers
draws each layer alone as it draws the stack."""
import dataclasses
import hashlib
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import manifest, reference, run as R, weights  # noqa: E402
from chipbench.model import KernelLayer  # noqa: E402

# the small configuration of test_chipbench_reference.py
SMALL = {"name": "small", "family": "dense", "hidden_size": 128,
         "num_hidden_layers": 3, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
         "vocab_size": 1000, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
SEED = 2 ** 33 + 12345
# sha256 digests at SMALL and SEED, recorded from commit a6ac6d588857 (the
# benchmark's last tree with one dense decoder built in): the bf16
# parameter tree, and the reference's and the fp8 control's logits over
# 40 tokens padded to 64.
GOLDEN = {
    "params": "71f6157793d7619eff8a3c090a70033959a55db2cb91eaddc4f0351a3de5034c",
    "rows": "5ccf641edda07e6de2a03dac27d81e112e48e968984a055cf93f13809230022d",
    "control_rows":
        "52c16d2100fb0f822189fb3006f6805ea00e591b93db2171fb722fcb81303bab",
}


def _tree_digest(params) -> str:
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.shape}{a.dtype}".encode())
        h.update(a.view(np.uint16).tobytes())
    return h.hexdigest()


def _rows_digest(rows) -> str:
    h = hashlib.sha256()
    for r in rows:
        h.update(np.ascontiguousarray(r, np.float32).tobytes())
    return h.hexdigest()


def _dense(conf=SMALL):
    return manifest.load_family(conf).dims(conf)


def test_dense_family_draws_and_computes_as_before():
    d = _dense()
    assert _tree_digest(weights.program_params(d, SEED)) == GOLDEN["params"]
    toks = np.random.default_rng(0).integers(1, d.vocab, 40).astype(np.int32)
    ref, ctl = reference.forward_rows(d, SEED, [(toks, np.arange(40))], 64,
                                      control=True)
    assert _rows_digest(ref) == GOLDEN["rows"]
    assert _rows_digest(ctl) == GOLDEN["control_rows"]


# -- the counts, against the formulas the benchmark had before families ------

PEAK = json.loads((ROOT / "chipbench" / "peaks.json").read_text())[
    "TPU v5 lite"]


def _old_decode_flops(d, ctx):
    D, H, KV, dh, F = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.d_ff
    per_layer = 2.0 * (D * H * dh + 2 * D * KV * dh + H * dh * D + 3 * D * F)
    return d.n_layers * (per_layer + 4.0 * H * dh * (ctx + 1)) \
        + 2.0 * D * d.vocab


def _old_prefill_flops(d, n):
    D, H, KV, dh, F = d.d_model, d.n_heads, d.n_kv_heads, d.head_dim, d.d_ff
    per_layer = 2.0 * (D * H * dh + 2 * D * KV * dh + H * dh * D + 3 * D * F)
    return d.n_layers * (n * per_layer + 4.0 * H * dh * (n * (n + 1) / 2.0)) \
        + 2.0 * D * d.vocab


@pytest.mark.parametrize("config", ["internlm2-1.8b", "codeqwen1.5-7b-d8"])
def test_dense_counts_are_the_old_formulas_to_the_bit(config):
    from collections import Counter
    d = _dense(manifest.load_json("configs", config))
    mfu = manifest.load_module("metrics", "step_mfu")
    roof = manifest.load_module("metrics", "decode_attention_roofline")
    layers = Counter(d.family.kernel_layers(d))
    for ctx in (0, 255, 1000, 2239):
        assert mfu.decode_flops(d, ctx) == _old_decode_flops(d, ctx)
        assert mfu.prefill_flops(d, ctx + 1) == _old_prefill_flops(d, ctx + 1)
        assert roof.step_needed_s(layers, ctx, PEAK) \
            == d.n_layers * roof.needed_s(d, ctx + 1, PEAK)


def test_a_windowed_layer_needs_only_its_window():
    roof = manifest.load_module("metrics", "decode_attention_roofline")
    full, local = KernelLayer(16, 8, 128), KernelLayer(16, 8, 128, 512)
    got = roof.step_needed_s({full: 1, local: 3}, 2000, PEAK)
    assert got == roof.needed_s(full, 2001, PEAK) \
        + 3 * roof.needed_s(full, 512, PEAK)


# -- a family with two stacks of different layers ----------------------------

TWO_STACKS = '''"""A test family: two leading layers of one leaf set, then three of
another, in the program's `dense_layers` and `layers` stacks; tied."""
from dataclasses import dataclass

from chipbench.model import Shape, shape_fields


@dataclass(frozen=True)
class Dims(Shape):
    pass


def dims(conf):
    return Dims(**shape_fields(conf))


def shared_leaves(d):
    return {"embedding": ((d.padded_vocab, d.d_model), 1.0),
            "final_norm": ((d.d_model,), 0.05)}


def stacks(d):
    return {"dense_layers": range(0, 2), "layers": range(2, d.n_layers)}


def layer_leaves(d, layer):
    D = d.d_model
    if layer < 2:
        return {"ln1": ((D,), 0.05), "mlp/wo": ((16, D), 0.1)}
    return {"ln1": ((D,), 0.05), "mix/conv": ((4, D), 0.3),
            "mix/w": ((D, 8), 0.2)}
'''


@pytest.fixture
def two_stacks(tmp_path):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "two_stacks.py").write_text(TWO_STACKS)
    conf = {"name": "two-stacks", "family": "two_stacks", "hidden_size": 32,
            "num_hidden_layers": 5, "vocab_size": 300, "rms_norm_eps": 1e-5,
            "torch_dtype": "bfloat16"}
    return manifest.load_family(conf, tmp_path).dims(conf)


def test_each_layer_drawn_alone_is_its_row_of_the_stack(two_stacks):
    d = two_stacks
    params = weights.program_params(d, SEED)
    assert sorted(params) == ["dense_layers", "embedding", "final_norm",
                              "layers"]
    key = weights.seed_key(SEED)
    for stack, layers in d.family.stacks(d).items():
        for row, layer in enumerate(layers):
            alone = weights.layer_f32(d, key, layer)
            assert sorted(alone) == sorted(d.family.layer_leaves(d, layer))
            for path, v in alone.items():
                node = params[stack]
                for p in path.split("/"):
                    node = node[p]
                assert np.array_equal(np.asarray(node[row], np.float32),
                                      np.asarray(v))
    sh = weights.shared_f32(d, key)
    assert sorted(sh) == ["embedding", "final_norm"]
    assert np.array_equal(np.asarray(params["embedding"], np.float32),
                          np.asarray(sh["embedding"]))
    # every layer has draws of its own
    ln1 = np.concatenate([np.asarray(params[s]["ln1"], np.float32)
                          for s in ("dense_layers", "layers")])
    assert len({r.tobytes() for r in ln1}) == d.n_layers


@pytest.mark.parametrize("stacks,error", [
    ({"layers": range(5)}, "differ in their leaves"),
    ({"dense_layers": range(0, 2), "layers": range(3, 5)},
     "do not hold each of the 5 layers once"),
])
def test_stacks_that_do_not_fit_the_layers_are_refused(two_stacks, stacks,
                                                        error, monkeypatch):
    d = two_stacks
    monkeypatch.setattr(d.family, "stacks", lambda d: stacks)
    with pytest.raises(ValueError, match=error):
        weights.program_params(d, SEED)


# -- a family added as files only --------------------------------------------

# StableLM-2's block, which the program runs with parallel_block=True:
# attention and the MLP read the same pre-norm, and there is no ln2.
PARALLEL = '''"""A parallel-block dense decoder (StableLM-2): attention and the
MLP both read the layer's one pre-norm; otherwise the dense family."""
import dataclasses

from chipbench.families import dense
from chipbench.families.dense import (  # noqa: F401
    decode_flops, head, kernel_layers, layer_kind, prefill_flops,
    shared_leaves, stacks)
from chipbench.reference import rms


@dataclasses.dataclass(frozen=True)
class Dims(dense.Dims):
    pass


def dims(conf):
    return Dims(**dataclasses.asdict(dense.dims(conf)))


def model_config(d):
    return dataclasses.replace(dense.model_config(d), parallel_block=True)


def layer_leaves(d, layer):
    leaves = dense.layer_leaves(d, layer)
    del leaves["ln2"]
    return leaves


def block(d, kind, x, W, mm):
    h = rms(x, W["ln1"], d.norm_eps)
    return x + dense.attention(d, h, W, mm) + dense.mlp(h, W, mm)
'''
TINY = {"name": "tiny-parallel", "family": "parallel_dense",
        "source": "https://huggingface.co/stabilityai/stablelm-2-1_6b",
        "reduced": [], "hidden_size": 64, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "intermediate_size": 128, "vocab_size": 500, "rope_theta": 1e4,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16"}
TRAFFIC = {"client": "closed_loop", "pattern": "sporadic", "clients": 1,
           "deck": 4, "prompt_len": {"values": [8, 16], "weights": [.5, .5]},
           "output_len": {"median": 6, "sigma": 0.5, "min": 4, "max": 8},
           "think_s": {"mean": 0.0}, "ramp_requests": 0}
CHECK = {"requests": 4, "min_tokens": 10, "logit_gap": 0.5}


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """Runs here compile on the CPU: keep them out of the checkout's
    compile cache, and leave the process's cache settings as they were."""
    import jax

    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


@pytest.fixture(scope="module")
def parallel_cell(tmp_path_factory):
    """A copy of the benchmark with the family, its configuration, a mix
    and a cell added as files and manifest entries, and the cell's parts
    as a run resolves them."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp)
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    root = tmp / "chipbench"
    (root / "families" / "parallel_dense.py").write_text(PARALLEL)
    (root / "configs" / "tiny-parallel.json").write_text(json.dumps(TINY))
    (root / "traffic" / "tiny_mix.json").write_text(json.dumps(TRAFFIC))
    (root / "workloads" / "tiny-parallel.tiny.json").write_text(
        json.dumps({"check": CHECK}))
    m = json.loads((tmp / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny-parallel", "source": TINY["source"],
                         "file": "chipbench/configs/tiny-parallel.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "tiny-parallel.tiny",
                           "config": "tiny-parallel", "traffic": "tiny_mix",
                           "chips": 1, "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    return manifest.resolve("tiny-parallel.tiny", False, repo=tmp, root=root)


def _run(parts):
    import jax
    args = R.parse_args(["--workload", "tiny-parallel.tiny",
                         "--seed", str(2 ** 33 + 7), "--seconds", "2"])
    return R.run_cell(args, parts, jax.devices(), PEAK)


def test_a_family_added_as_files_runs_a_whole_cell(parallel_cell):
    fam = parallel_cell["family"]
    d = fam.dims(parallel_cell["config"])
    assert d.family is fam and fam.model_config(d).parallel_block
    out = _run(parallel_cell)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "tok_s" in out["metrics"]


def test_the_dense_block_in_its_place_is_not_correct(parallel_cell,
                                                     monkeypatch):
    """The sequential dense block, given the parallel layer's weights
    (its second norm at unit scale, as the layer has none), as the
    reference of the parallel program."""
    import jax.numpy as jnp
    dense = manifest.load_module("families", "dense")

    def sequential(d, kind, x, W, mm):
        return dense.block(d, kind, x,
                           dict(W, ln2=jnp.zeros_like(W["ln1"])), mm)
    monkeypatch.setattr(parallel_cell["family"], "block", sequential)
    out = _run(parallel_cell)
    assert not out["correct"], out["check"]
    gap = out["check"]["logit_gap"]
    assert gap["value"] > gap["limit"]
