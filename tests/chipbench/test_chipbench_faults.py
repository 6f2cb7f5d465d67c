"""A whole run of the chip benchmark past its look for a chip, on the CPU
at a small size: sound, it comes out correct; with the timed path broken
underneath, or with the fp8 control in the program's place, `correct`
comes out false. The faults are those a one-slot,
one-chip serving cell can have: a decode step that hands back its state
unadvanced, and a token altered where it is produced. (Half a batch left
out and a missing exchange between chips need a batch and a mesh that
these cells do not have.)"""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import manifest, run as R  # noqa: E402

SMALL = {"name": "small", "hidden_size": 64, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "intermediate_size": 128, "vocab_size": 500, "rope_theta": 1e4,
         "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
         "torch_dtype": "bfloat16"}
TRAFFIC = {"client": "closed_loop", "pattern": "sporadic", "clients": 1,
           "deck": 4, "prompt_len": {"values": [8, 16], "weights": [.5, .5]},
           "output_len": {"median": 6, "sigma": 0.5, "min": 4, "max": 8},
           "think_s": {"mean": 0.0}, "ramp_requests": 0}
CELL = json.loads((ROOT / "chipbench" / "workloads"
                   / "internlm2-1.8b.sporadic.json").read_text())


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    """The runs here compile on the CPU: keep them out of the checkout's
    compile cache, and leave the process's cache settings as they were."""
    import jax

    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def _run(check=None, control=False):
    import jax
    parts = {"entry": {"chips": 1}, "config": SMALL,
             "family": manifest.load_module("families", "dense"),
             "traffic": TRAFFIC,
             "cell": {"check": check or dict(CELL["check"], min_tokens=10)},
             "client": manifest.load_module("clients", "closed_loop"),
             "metrics": []}
    args = R.parse_args(["--workload", "small", "--seed", str(2 ** 33 + 5),
                         "--seconds", "2"])
    peak = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    return R.run_cell(args, parts, jax.devices(), peak["TPU v5 lite"],
                      control=control)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0


def _unadvanced(orig):
    def decode_step(self, state, tokens):
        logits, new = orig(self, state, tokens)
        new = dict(new)
        new["glob"] = state["glob"]           # position never moves on
        return logits, new
    return decode_step


def _altered(orig):
    def _sample(self, logits):
        tok = orig(self, logits)
        return (tok + 1) % self.cfg.vocab_size
    return _sample


@pytest.mark.parametrize("fault", ["unadvanced_state", "altered_token"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.core.engine import InterleavedEngine
    from repro.serving.backend import EngineBackend
    if fault == "unadvanced_state":
        monkeypatch.setattr(InterleavedEngine, "decode_step",
                            _unadvanced(InterleavedEngine.decode_step))
    else:
        monkeypatch.setattr(EngineBackend, "_sample",
                            _altered(EngineBackend._sample))
    out = _run()
    assert not out["correct"], out["check"]
    assert out["check"]["logit_gap"]["value"] \
        > out["check"]["logit_gap"]["limit"]


def test_control_in_the_programs_place_is_not_correct():
    """The fp8 control, judged by the rule that judges the program, over
    every request finished in the window. At this size sound runs read
    0.006-0.028 and the control 0.15-0.51 (three seeds, windows of 0.5
    and 2 s, on the CPU), so the limit here is 0.07; the cells' own
    limits come from readings at their size on the chip."""
    out = _run(check={"requests": 100, "min_tokens": 10, "logit_gap": 0.07},
               control=True)
    assert out["correct"], out["check"]
    assert not out["control"]["correct"], out["control"]
    ctl = out["control"]["check"]["logit_gap"]
    assert ctl["value"] > ctl["limit"]
