"""The benchmark's float32 reference against the program at a small size
on the CPU, its weights against the program's parameter layout, and its
fp8 control: the control must read well above what the program reads."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from chipbench import reference, weights  # noqa: E402
from chipbench.families.dense import dims, model_config  # noqa: E402

SMALL = {"name": "small", "hidden_size": 128, "num_hidden_layers": 3,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "intermediate_size": 256, "vocab_size": 1000,
         "rope_theta": 1e6, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
SEED = 2 ** 33 + 12345


@pytest.fixture(scope="module")
def small():
    import jax.numpy as jnp

    from repro.models import model as M
    d = dims(SMALL)
    cfg = model_config(d)
    params = weights.program_params(d, SEED)
    toks = np.random.default_rng(0).integers(1, d.vocab, 40).astype(np.int32)
    logits, _ = M.forward(cfg, params, jnp.asarray(toks)[None])
    prog = np.asarray(logits[0, :, :d.vocab], np.float32)
    return d, cfg, params, toks, prog


def test_program_params_have_the_program_layout(small):
    import jax

    from repro.models import model as M
    d, cfg, params, _, _ = small
    want = M.param_shapes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_layer_draws_equal_the_stacked_draw(small):
    d, _, params, _, _ = small
    key = weights.seed_key(SEED)
    for layer in range(d.n_layers):
        got = weights.layer_f32(d, key, layer)
        for path, v in got.items():
            node = params["layers"]
            for p in path.split("/"):
                node = node[p]
            assert np.array_equal(np.asarray(node[layer], np.float32),
                                  np.asarray(v))
    sh = weights.shared_f32(d, key)
    assert np.array_equal(np.asarray(params["lm_head"], np.float32),
                          np.asarray(sh["lm_head"]))


def test_seeds_past_32_bits_differ():
    a = np.asarray(weights.seed_key(5))
    b = np.asarray(weights.seed_key(5 + 2 ** 32))
    assert not np.array_equal(a, b)


def test_reference_agrees_with_the_program(small):
    d, _, _, toks, prog = small
    rows = np.arange(len(toks))
    ref = reference.forward_rows(d, SEED, [(toks, rows)], 64)[0]
    diff = np.sqrt(np.mean((prog - ref) ** 2)) / np.sqrt(np.mean(ref ** 2))
    assert diff < 0.05, diff             # bf16 program, f32 reference
    gaps = reference.served_gaps(ref, np.argmax(prog, -1))
    assert gaps.max() < 0.2, gaps.max()


def test_padding_leaves_the_rows_alone(small):
    d, _, _, toks, _ = small
    rows = np.arange(len(toks))
    a = reference.forward_rows(d, SEED, [(toks, rows)], len(toks))[0]
    b = reference.forward_rows(d, SEED, [(toks, rows)], 96)[0]
    assert np.allclose(a, b, rtol=1e-4, atol=1e-4)


def test_fp8_control_reads_far_above_the_program(small):
    d, _, _, toks, prog = small
    rows = np.arange(len(toks))
    ref, ctl = reference.forward_rows(d, SEED, [(toks, rows)], 64,
                                      control=True)
    program = reference.served_gaps(ref[0], np.argmax(prog, -1)).max()
    control = reference.control_gaps(ref[0], ctl[0]).max()
    assert control > 3 * program and control > 0.05, (program, control)
