"""The FLOP and byte counts behind step_mfu and the decode-attention
roofline, against hand counts for both benchmark configurations."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import manifest, window as W  # noqa: E402
from chipbench.families.dense import dims  # noqa: E402

PEAK = json.loads((ROOT / "chipbench" / "peaks.json").read_text())[
    "TPU v5 lite"]

# hand counts from the published widths
HAND = {
    # D, layers, H, KV, dh, F, V
    "internlm2-1.8b": (2048, 24, 16, 8, 128, 8192, 92544),
    "codeqwen1.5-7b-d8": (4096, 8, 32, 4, 128, 13440, 92416),
}


@pytest.fixture(params=sorted(HAND))
def cfg(request):
    return request.param, dims(manifest.load_json("configs", request.param))


def test_config_widths_are_the_published_ones(cfg):
    name, d = cfg
    D, L, H, KV, dh, F, V = HAND[name]
    assert (d.d_model, d.n_layers, d.n_heads, d.n_kv_heads, d.head_dim,
            d.d_ff, d.vocab) == (D, L, H, KV, dh, F, V)


def test_decode_and_prefill_flops(cfg):
    name, d = cfg
    mfu = manifest.load_module("metrics", "step_mfu")
    D, L, H, KV, dh, F, V = HAND[name]
    per_layer = 2 * (D * H * dh + 2 * D * KV * dh + H * dh * D + 3 * D * F)
    ctx = 1000
    want = L * (per_layer + 4 * H * dh * (ctx + 1)) + 2 * D * V
    assert mfu.decode_flops(d, ctx) == pytest.approx(want, rel=1e-12)
    n = 512
    want = L * (n * per_layer + 4 * H * dh * n * (n + 1) / 2) + 2 * D * V
    assert mfu.prefill_flops(d, n) == pytest.approx(want, rel=1e-12)


def test_step_mfu_over_the_traced_window(cfg):
    name, d = cfg
    mfu = manifest.load_module("metrics", "step_mfu")
    steps = [(0.0, 0.2, "prefill", [256]),
             (0.2, 0.25, "decode", [256]),
             (0.25, 0.3, "decode", [257]),
             (0.3, 0.35, "decode", [258])]
    w = W.Window([], 0.0, 1.0, steps)
    run = type("R", (), dict(window=w, trace=object(), traced_open_s=0.22,
                             dims=d, chips=1, peak=PEAK))()
    # steps that end inside [0.22, 1.0]: the three decode steps
    flops = sum(mfu.decode_flops(d, c) for c in (256, 257, 258))
    want = 100 * flops / (0.78 * PEAK["bf16_flops_per_s"])
    assert mfu.read(run) == pytest.approx(want)


def test_attention_roofline_needed_time(cfg):
    name, d = cfg
    roof = manifest.load_module("metrics", "decode_attention_roofline")
    D, L, H, KV, dh, F, V = HAND[name]
    keys = 1024
    bytes_ = 2 * keys * KV * dh * 2 + 2 * H * dh * 2
    flops = 4 * H * dh * keys
    want = max(flops / 197e12, bytes_ / 819e9)
    assert bytes_ / 819e9 > flops / 197e12      # memory-bound at decode
    assert roof.needed_s(d, keys, PEAK) == pytest.approx(want, rel=1e-12)
    # one step of one live request at ctx 1023 whose kernel events took
    # twice the least time: half the roofline
    from chipbench.tracereduce import Trace
    kern = L * want * 2
    tr = Trace(ops={0: [("decode_attention.1", 10.0, 10.0 + kern),
                        ("fusion.3", 10.0 + kern, 11.0)]})
    w = W.Window([], 0.0, 1.0, [(0.5, 0.6, "decode", [keys - 1])])
    run = type("R", (), dict(window=w, trace=tr, traced_open_s=0.0,
                             traced=[(9.0, 12.0)], dims=d, peak=PEAK))()
    assert roof.read(run) == pytest.approx(50.0)


def test_roofline_reads_nothing_without_kernel_events(cfg):
    name, d = cfg
    roof = manifest.load_module("metrics", "decode_attention_roofline")
    from chipbench.tracereduce import Trace
    w = W.Window([], 0.0, 1.0, [(0.5, 0.6, "decode", [100])])
    run = type("R", (), dict(window=w, trace=Trace(ops={0: []}),
                             traced_open_s=0.0, traced=[(0.0, 1.0)],
                             dims=d, peak=PEAK))()
    assert roof.read(run) is None
    assert np.isfinite(roof.needed_s(d, 1, PEAK))
