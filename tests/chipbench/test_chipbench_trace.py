"""The reduction from a profiler trace to per-layer numbers: union of
busy intervals, idle gaps named by the host span they fell in, and time
per program, on a hand-made trace and on a small recorded one (0.25 s of
a traced run of internlm2-1.8b.sporadic on one v5e chip)."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import manifest, tracereduce as R  # noqa: E402

RECORDED = ROOT / "chipbench" / "testdata" / "trace_small.json"


def _hand():
    return R.Trace(
        ops={0: [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 2.5),
                 ("decode_attention.3", 3.0, 3.5), ("fusion.1", 6.0, 7.0)]},
        modules={0: [("jit_fetch_fn", 1.0, 1.4), ("jit_step_fn", 1.4, 3.5),
                     ("jit_step_fn", 6.0, 7.0)]},
        host=[("cb.traced", 0.0, 10.0), ("cb.sched.step", 0.1, 4.0),
              ("cb.engine.decode", 0.2, 1.2), ("cb.client.wait", 4.0, 5.9),
              ("cb.sched.step", 5.9, 7.5), ("cb.backend.sample", 7.0, 7.5)])


def test_union_intersect_gaps():
    assert R.union([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]
    assert R.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert R.gaps([(1, 2), (3, 4)], [(0, 5)]) == [(0, 1), (2, 3), (4, 5)]
    assert R.gaps([(1, 2)], [(0, 1.5), (3, 4)]) == [(0, 1), (3, 4)]
    assert R.length(R.union([(0, 1), (0.5, 2)])) == 2


def test_busy_and_idle_on_a_hand_trace():
    tr = _hand()
    within = [(0.0, 10.0)]
    assert R.length(R.busy(tr, 0, within)) == pytest.approx(1.5 + 0.5 + 1.0)
    live = R.intersect(R.union([(a, b) for _, a, b in
                                tr.span("cb.sched.step")]), within)
    assert R.length(live) == pytest.approx(3.9 + 1.6)
    bd = R.breakdown(tr, within)
    assert bd["device_ops"][0] == ["fusion.1", 2.0]
    idle = dict((k, v) for k, v in bd["idle_gaps"])
    # each gap goes to the innermost span at its midpoint: 0-1 (0.5:
    # decode), 2.5-3 (step), 3.5-6 (4.75: client wait), 7-10 (8.5: traced)
    assert idle["cb.engine.decode"] == pytest.approx(1.0)
    assert idle["cb.sched.step"] == pytest.approx(0.5)
    assert idle["cb.client.wait"] == pytest.approx(2.5)
    assert idle["cb.traced"] == pytest.approx(3.0)


def test_host_names_pick_the_innermost_span():
    host = [("cb.traced", 0, 10), ("cb.sched.step", 1, 5),
            ("cb.engine.decode", 2, 3)]
    assert R.host_names([(2.2, 2.4), (4, 4.2), (9, 9.5), (11, 12)],
                        host) == ["cb.engine.decode", "cb.sched.step",
                                  "cb.traced", "no span"]


def _run(tr, within, chips=1):
    return type("Run", (), dict(trace=tr, traced=within, chips=chips))()


def test_device_metrics_on_a_hand_trace():
    tr = _hand()
    idle = manifest.load_module("metrics", "device_idle")
    step = manifest.load_module("metrics", "decode_step_ms")
    # live 5.5 s, busy inside it: 1-2.5, 3-3.5, 6-7 = 3.0 s
    assert idle.read(_run(tr, [(0.0, 10.0)])) == pytest.approx(
        100 * (1 - 3.0 / 5.5))
    # fetch 0.4 + steps 2.1 + 1.0 over 2 step executions
    assert step.read(_run(tr, [(0.0, 10.0)])) == pytest.approx(
        1e3 * 3.5 / 2)


def test_json_round_trip_and_clip():
    tr = _hand()
    back = R.Trace.from_json(tr.to_json())
    assert back.ops == tr.ops and back.host == tr.host
    small = tr.clipped(2.6, 3.2)
    assert [e[0] for e in small.ops[0]] == ["decode_attention.3"]


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.is_file():
        pytest.fail(f"missing recording {RECORDED}")
    return R.Trace.from_json(RECORDED.read_text())


def _grid_busy(evs, a, b, step=1e-6):
    t = np.arange(a, b, step) + step / 2
    on = np.zeros(t.shape, bool)
    for _, s, e in evs:
        on[(t >= s) & (t < e)] = True
    return on.sum() * step


def test_recorded_trace_busy_matches_a_brute_force_count(recorded):
    tr = recorded
    host = tr.span("cb.traced")
    lo = min(a for evs in tr.ops.values() for _, a, _ in evs)
    hi = max(b for evs in tr.ops.values() for _, _, b in evs)
    within = [(lo, hi)]
    for dev, evs in tr.ops.items():
        got = R.length(R.busy(tr, dev, within))
        assert got == pytest.approx(_grid_busy(evs, lo, hi), abs=2e-5 * len(evs) + 1e-4)
    bd = R.breakdown(tr, within)
    total_idle = sum(v for _, v in bd["idle_gaps"])
    n = len(tr.ops)
    assert total_idle == pytest.approx(
        n * (hi - lo) - sum(R.length(R.busy(tr, d, within)) for d in tr.ops),
        rel=1e-6) or len(bd["idle_gaps"]) == 10
    assert host or tr.host, "the recording holds the benchmark's spans"


def test_recorded_trace_has_step_programs_and_kernel(recorded):
    names = {n for evs in recorded.modules.values() for n, _, _ in evs}
    step = manifest.load_module("metrics", "decode_step_ms")
    assert any(step.STEP in n for n in names), names
    roof = manifest.load_module("metrics", "decode_attention_roofline")
    ops = {n for evs in recorded.ops.values() for n, _, _ in evs}
    assert any(roof.KERNEL in n for n in ops), sorted(ops)[:20]
