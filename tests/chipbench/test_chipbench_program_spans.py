"""The readers of the program's own spans and step-program scopes
(`chipbench/program_trace.py`, `metrics/decode_host_gap_ms.py`,
`metrics/chunk_assembly_ms.py`) on hand-built runs: ring events on the
program's clock, device ops on the profiler's."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import manifest, tracereduce as R  # noqa: E402

START_NS = 10 ** 18                # the profiler session's start
PIPE = "pipeline"


@pytest.fixture
def P(monkeypatch):
    """program_trace with no tracer of its own: each test hands it one."""
    from chipbench import program_trace
    monkeypatch.setattr(program_trace, "TRACER", None)
    monkeypatch.setattr(program_trace, "PROFILE_START_NS", START_NS)
    return program_trace


def _tracer():
    from repro.obs.trace import Tracer
    tr = Tracer(clock=lambda: 0.0)
    # ring time 50.0 is 2.0 s into the profiler session
    tr.instant("clock.sync", ts=50.0, args={"time_ns": START_NS + 2 * 10 ** 9})
    return tr


def _decode_step(tr, t):
    """One decode step at ring time t (ms offsets as in the docstring of
    `test_host_gap_and_its_split`)."""
    for name, a, b in (("sched.step", 0, 30), ("engine.decode", 1, 29),
                       ("engine.dispatch", 2, 10), ("engine.fetch", 3, 4),
                       ("engine.step", 5, 9), ("backend.sample", 11, 12),
                       ("backend.sync", 13, 28)):
        tr.complete(name, ts=t + a * 1e-3, dur=(b - a) * 1e-3, track=PIPE)


def _run(trace, within=(0.0, 10.0), chips=1):
    return type("Run", (), dict(trace=trace, traced=[within],
                                chips=chips))()


def _gap_case(busy_dev1=False):
    tr = _tracer()
    for k in range(3):
        _decode_step(tr, 50.100 + 0.040 * k)
    tr.complete("sched.step", ts=50.300, dur=0.05, track=PIPE)
    tr.complete("engine.prefill", ts=50.301, dur=0.048, track=PIPE)
    # a request's lifecycle span covers everything, on its own track
    tr.complete("req.decode", ts=50.0, dur=1.0, track="req:0")
    ops = {0: [("fusion.%d" % k, 2.104 + 0.040 * k, 2.127 + 0.040 * k)
               for k in range(3)]}
    if busy_dev1:
        ops[1] = [("fusion.9", 2.0, 2.4)]
    host = [("cb.sched.step", 2.0999 + 0.040 * k, 2.131 + 0.040 * k)
            for k in range(3)] + [("cb.sched.step", 2.2999, 2.351)]
    return tr, R.Trace(ops=ops, modules={}, host=host)


def test_ring_times_map_onto_the_profiler_clock_across_a_skew(P):
    from repro.obs.trace import Tracer
    tr = Tracer(clock=lambda: 0.0)
    tr.instant("clock.sync", ts=10.0, args={"time_ns": START_NS + 10 ** 9})
    # the backend skipped ahead: ring +10 s while the wall moved 4 s
    tr.instant("clock.sync", ts=20.0,
               args={"time_ns": START_NS + 5 * 10 ** 9})
    at = P.to_profiler(tr.events(), START_NS)
    assert at(15.0) == pytest.approx(6.0)
    assert at(25.0) == pytest.approx(10.0)
    assert at(5.0) == pytest.approx(-4.0)
    assert P.to_profiler([], START_NS) is None


def test_host_gap_and_its_split(P, monkeypatch, capsys):
    """Steps start every 40 ms; in each, the device is busy 4-27 ms after
    the step's start. Idle per step: 0-4 ms (sched.step 0-1, decode 1-2,
    dispatch 2-3, fetch 3-4) and 27-40 ms (sync 27-28, decode 28-29,
    sched.step 29-30, the benchmark's loop 30-40): 17 ms. The third step
    is followed by an admission, so two steps count."""
    gap = manifest.load_module("metrics", "decode_host_gap_ms")
    tr, trace = _gap_case()
    monkeypatch.setattr(P, "TRACER", tr)
    run = _run(trace)
    assert gap.read(run) == pytest.approx(17.0)
    split, n = gap.idle_split(run, P.spans())
    assert n == 2
    want = {"sched.step": 2.0, "engine.decode": 2.0, "engine.dispatch": 1.0,
            "engine.fetch": 1.0, "backend.sync": 1.0, gap.OUTSIDE: 10.0}
    assert {k: 1e3 * v for k, v in split.items()} == pytest.approx(want)
    # the program's step starts 0.1 ms after the benchmark's wrap
    assert gap.clock_offsets_us(run, P.spans()) == pytest.approx(
        [100.0] * 4)
    assert "decode_host_gap_ms split" in capsys.readouterr().err


def test_host_gap_averages_over_chips(P, monkeypatch):
    gap = manifest.load_module("metrics", "decode_host_gap_ms")
    tr, trace = _gap_case(busy_dev1=True)
    monkeypatch.setattr(P, "TRACER", tr)
    assert gap.read(_run(trace, chips=2)) == pytest.approx(8.5)


def test_host_gap_reads_nothing_without_the_programs_spans(P, monkeypatch):
    gap = manifest.load_module("metrics", "decode_host_gap_ms")
    tr, trace = _gap_case()
    assert gap.read(_run(trace)) is None              # no tracer
    monkeypatch.setattr(P, "TRACER", tr)
    assert gap.read(_run(trace, within=(5.0, 10.0))) is None  # no steps
    assert gap.read(_run(None)) is None               # untraced run
    from repro.obs.trace import Tracer
    bare = Tracer(clock=lambda: 0.0)                  # spans, no sync
    _decode_step(bare, 50.1)
    _decode_step(bare, 50.14)
    monkeypatch.setattr(P, "TRACER", bare)
    assert gap.read(_run(trace)) is None


def _scope_case():
    tr = _tracer()
    tr.instant("engine.scopes", track="engine", args={
        "module": "jit_step_fn", "program": "1",
        "ops": {"fusion.1": "lime.chunk_params", "fusion.2": "lime.layers"}})
    tr.instant("engine.scopes", track="engine", args={
        "module": "jit_fetch_fn", "program": "fetch",
        "ops": {"all-to-all.1": "lime.restore"}})
    ops, mods = [], []
    for t in (1.0, 2.0):
        mods += [("jit_fetch_fn(9)", t, t + 0.1),
                 ("jit_step_fn(7)", t + 0.1, t + 0.5)]
        ops += [("all-to-all.1", t, t + 0.08),
                ("while.3", t + 0.1, t + 0.45),       # holds the next two
                ("fusion.1", t + 0.1, t + 0.2),
                ("fusion.2", t + 0.2, t + 0.4),
                ("copy.1", t + 0.45, t + 0.5)]        # outside every part
    # another program's op of the same name is not the step's
    mods.append(("jit_other(3)", 1.6, 1.7))
    ops.append(("fusion.1", 1.6, 1.7))
    return tr, R.Trace(ops={0: sorted(ops, key=lambda e: e[1])},
                       modules={0: mods}, host=[])


def test_chunk_assembly_per_step_and_every_part(P, monkeypatch, capsys):
    asm = manifest.load_module("metrics", "chunk_assembly_ms")
    tr, trace = _scope_case()
    monkeypatch.setattr(P, "TRACER", tr)
    run = _run(trace, within=(0.9, 3.0))
    assert asm.read(run) == pytest.approx(100.0)
    parts, steps = asm.per_step(run, P.scopes(), 0)
    assert steps == 2
    assert {k: 1e3 * v for k, v in parts.items()} == pytest.approx(
        {"lime.chunk_params": 100.0, "lime.layers": 200.0,
         "lime.restore": 80.0, asm.OUTSIDE: 50.0})
    assert "step parts" in capsys.readouterr().err


def test_leaves_drop_the_ops_that_hold_others():
    asm = manifest.load_module("metrics", "chunk_assembly_ms")
    ops = [("while.1", 0.0, 1.0), ("a", 0.0, 0.4), ("b", 0.4, 1.0),
           ("c", 1.0, 1.2), ("d", 1.19999, 1.3)]    # c, d touch, not nest
    assert [e[0] for e in asm.leaves(ops)] == ["a", "b", "c", "d"]


def test_chunk_assembly_reads_nothing_without_scopes(P, monkeypatch):
    asm = manifest.load_module("metrics", "chunk_assembly_ms")
    tr, trace = _scope_case()
    assert asm.read(_run(trace, within=(0.9, 3.0))) is None   # no tracer
    monkeypatch.setattr(P, "TRACER", _tracer())               # no scopes
    assert asm.read(_run(trace, within=(0.9, 3.0))) is None
    monkeypatch.setattr(P, "TRACER", tr)
    assert asm.read(_run(trace, within=(5.0, 6.0))) is None   # no steps


def test_tracer_installed_only_where_the_program_offers_clock_sync(
        P, monkeypatch):
    from repro.obs import trace as T
    P._install()
    assert P.TRACER is T.get_tracer() and P.TRACER.capacity == P.CAPACITY
    T.set_tracer(None)
    monkeypatch.setattr(P, "TRACER", None)
    monkeypatch.delattr(T, "CLOCK_SYNC")
    P._install()
    assert P.TRACER is None and T.get_tracer() is None


def test_profile_start_is_read_from_the_trace_loaded(P, tmp_path,
                                                     monkeypatch):
    """The session's start on the profiler's clock is time.time_ns at
    start_trace, and the benchmark's load of the trace records it."""
    import jax
    monkeypatch.setattr(P, "PROFILE_START_NS", None)
    monkeypatch.setattr(R, "load_xplane", lambda path: "loaded")
    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    after = time.time_ns()
    with jax.profiler.TraceAnnotation("cb.probe"):
        pass
    jax.profiler.stop_trace()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    P._hook_trace_load()
    assert R.load_xplane(path) == "loaded"
    assert before <= P.PROFILE_START_NS <= after
