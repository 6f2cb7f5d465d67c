"""Observability: flight-recorder tracer, exporters, MetricsRegistry,
and the registry-derived ServingReport (DESIGN.md §15)."""
import math

import pytest

from repro.configs.registry import get_config
from repro.core.cost_model import CostEnv, Workload
from repro.core.profiles import env_E3, mbps
from repro.obs import trace as tr_ev
from repro.obs.exporters import (export_jsonl, read_jsonl, to_chrome,
                                 validate_chrome)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import (EVT_ARGS, EVT_DUR, EVT_NAME, EVT_PH, EVT_TRACK,
                             EVT_TS, Tracer, get_tracer, tracing)
from repro.serving import (ContinuousBatchingScheduler, Request,
                           SchedulerConfig, SimBackend, cli_arrivals,
                           requests_from_arrivals, summarize)
from repro.serving.metrics import (SCHEMA_VERSION, percentile,
                                   report_from_dict)


# ----------------------------------------------------------------------------
# Tracer ring semantics
# ----------------------------------------------------------------------------
def test_ring_keeps_last_n_and_counts_drops():
    tr = Tracer(capacity=4, clock=lambda: 0.0)
    for i in range(10):
        tr.instant(f"e{i}", track="t")
    assert len(tr) == 4
    assert tr.emitted == 10
    assert tr.dropped == 6
    # flight-recorder semantics: the LAST events survive
    assert [e[EVT_NAME] for e in tr.events()] == ["e6", "e7", "e8", "e9"]


def test_capacity_must_be_positive():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_phases_and_explicit_timestamps():
    tr = Tracer(clock=lambda: 7.0)
    tr.instant("a", track="t")                      # clock-stamped
    tr.instant("b", ts=1.5, track="t")              # explicit ts wins
    tr.complete("c", ts=2.0, dur=0.5, track="t")
    tr.complete("neg", ts=2.0, dur=-1.0, track="t")  # clamped, not invalid
    tr.begin("d", track="t")
    tr.end("d", track="t")
    tr.counter("e", track="t", pages=3)
    evs = tr.events()
    assert [e[EVT_PH] for e in evs] == ["i", "i", "X", "X", "B", "E", "C"]
    assert evs[0][EVT_TS] == 7.0
    assert evs[1][EVT_TS] == 1.5
    assert evs[3][EVT_DUR] == 0.0
    assert evs[6][EVT_ARGS] == {"pages": 3}


def test_span_context_manager():
    t = {"now": 1.0}
    tr = Tracer(clock=lambda: t["now"])
    with tr.span("work", track="t"):
        t["now"] = 3.5
    (e,) = tr.events()
    assert e[EVT_PH] == "X" and e[EVT_TS] == 1.0 and e[EVT_DUR] == 2.5


def test_global_install_and_restore():
    assert get_tracer() is None
    with tracing() as tr:
        assert get_tracer() is tr
        with tracing() as inner:
            assert get_tracer() is inner
        assert get_tracer() is tr       # nested install restores previous
    assert get_tracer() is None


# ----------------------------------------------------------------------------
# exporters
# ----------------------------------------------------------------------------
def _sample_tracer():
    tr = Tracer(clock=lambda: 0.0)
    tr.instant(tr_ev.REQ_ARRIVE, ts=0.0, track=tr_ev.req_track(0),
               args={"prompt_len": 8})
    tr.complete(tr_ev.REQ_SPAN, ts=0.0, dur=2.0, track=tr_ev.req_track(0))
    tr.complete(tr_ev.STAGE_COMPUTE, ts=0.1, dur=0.2,
                track=tr_ev.dev_track(1))
    tr.complete(tr_ev.STEP, ts=0.0, dur=0.5, track=tr_ev.TRACK_PIPELINE)
    tr.counter("kv_pages", ts=0.3, track=tr_ev.TRACK_KV, device=4)
    return tr


def test_jsonl_round_trip(tmp_path):
    tr = _sample_tracer()
    p = str(tmp_path / "t.jsonl")
    n = export_jsonl(tr, p)
    assert n == len(tr.events())
    header, evs = read_jsonl(p)
    assert header["schema"] == "lime-trace"
    assert evs == tr.events()           # lossless, in-memory layout


def test_chrome_export_valid_and_track_mapping():
    doc = to_chrome(_sample_tracer())
    assert validate_chrome(doc) == []
    by_name = {}
    for e in doc["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e)
    # pid mapping: req:* -> "requests" (2), dev:* -> "fleet" (1), rest -> 0
    assert by_name[tr_ev.REQ_SPAN][0]["pid"] == 2
    assert by_name[tr_ev.STAGE_COMPUTE][0]["pid"] == 1
    assert by_name[tr_ev.STEP][0]["pid"] == 0
    # seconds -> microseconds
    assert by_name[tr_ev.REQ_SPAN][0]["dur"] == pytest.approx(2e6)
    # metadata names every track
    thread_names = {e["args"]["name"] for e in by_name["thread_name"]}
    assert {"req:0", "dev:1", "pipeline", "kv"} <= thread_names


def test_validate_chrome_catches_problems():
    assert validate_chrome({}) == ["missing top-level 'traceEvents'"]
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 0, "tid": 0, "ts": 1.0, "dur": -4},
        {"name": "y", "ph": "E", "pid": 0, "tid": 0, "ts": 1.0},
        {"name": "z", "ph": "B", "pid": 0, "tid": 1, "ts": 1.0},
    ]}
    problems = validate_chrome(bad)
    assert any("dur" in p for p in problems)
    assert any("E without matching B" in p for p in problems)
    assert any("unclosed B" in p for p in problems)


# ----------------------------------------------------------------------------
# MetricsRegistry
# ----------------------------------------------------------------------------
def test_registry_instruments():
    m = MetricsRegistry()
    m.inc("served")
    m.inc("served", 2)
    m.set("adopted", 41.0)
    m.set_gauge("peak_active", 3)
    m.set_gauge("peak_active", 7)
    m.set_gauge("peak_active", 2)       # peak sticks at the high-water mark
    m.set_gauge("depth", 5)
    m.set_gauge("depth", 1)             # non-peak gauge reports last value
    for v in (1.0, 2.0, 3.0, 4.0):
        m.observe("lat", v)
    d = m.to_stats_dict()
    assert d["served"] == 3
    assert d["adopted"] == 41.0
    assert d["peak_active"] == 7
    assert d["depth"] == 1
    assert d["lat_p50"] == 2.0 and d["lat_p99"] == 4.0 and d["lat_count"] == 4
    m.update({"spec_drafted": 10, "spec_accepted": 6})
    assert m.get("spec_drafted") == 10
    assert m.get("missing", -1.0) == -1.0


def test_histogram_percentile_matches_serving_convention():
    m = MetricsRegistry()
    h = m.histogram("x")
    assert math.isnan(h.percentile(50))
    for v in (5.0, 1.0, 3.0):
        h.observe(v)
    for p in (0, 1, 50, 99, 100):
        assert h.percentile(p) == percentile([5.0, 1.0, 3.0], p)


def _finished(rid, arrival, admitted, first, finish, generated):
    r = Request(rid, None, max_new_tokens=generated, arrival_s=arrival,
                prompt_len=16)
    r.admitted_s = admitted
    r.first_token_s = first
    r.finish_s = finish
    r.generated = generated
    r.output = list(range(generated))
    r.done = True
    return r


def test_registry_report_field_identical_to_legacy_dict():
    """The acceptance bar for the stats refactor: summarize() over a
    MetricsRegistry and over the flat dict it replaces produce the same
    ServingReport, field for field."""
    reqs = [_finished(0, 0.0, 0.1, 0.5, 2.0, 8),
            _finished(1, 0.2, 0.3, 0.9, 3.0, 8)]
    legacy = {"peak_active": 2, "peak_kv_pages": 5, "kv_pages_spilled": 1,
              "kv_pages_fetched": 1, "kv_migrated_bytes": 4096.0,
              "spec_rounds": 3, "spec_drafted": 12, "spec_accepted": 9,
              "prefix_lookups": 2, "prefix_hits": 1, "cached_tokens": 64,
              "prefill_tokens_saved": 64, "retier_events": 2,
              "layers_demoted": 1, "layers_promoted": 1,
              "hbm_returned_bytes": 1e6, "retier_reclaimed_pages": 2}
    reg = MetricsRegistry()
    for k, v in legacy.items():
        if k.startswith("peak_"):
            reg.set_gauge(k, v)
        else:
            reg.set(k, v)
    a = summarize(reqs, pattern="p", backend="b", stats=legacy).to_dict()
    b = summarize(reqs, pattern="p", backend="b", stats=reg).to_dict()
    assert set(a) == set(b)
    for k in a:
        if isinstance(a[k], float) and math.isnan(a[k]):
            assert math.isnan(b[k]), k
        else:
            assert a[k] == b[k], k


# ----------------------------------------------------------------------------
# summarize edge cases + schema tolerance
# ----------------------------------------------------------------------------
def test_summarize_nothing_served():
    rep = summarize([])
    assert rep.n_requests == 0 and rep.span_s == 0.0
    assert math.isnan(rep.ms_per_token)
    assert rep.throughput_tok_s == 0.0 and rep.throughput_req_s == 0.0
    assert math.isnan(rep.ttft_p50_s) and math.isnan(rep.latency_p99_s)


def test_summarize_all_rejected():
    reqs = []
    for i in range(3):
        r = Request(i, None, max_new_tokens=4, arrival_s=float(i))
        r.rejected = True
        reqs.append(r)
    rep = summarize(reqs)
    assert rep.n_requests == 0 and rep.n_rejected == 3
    assert math.isnan(rep.ms_per_token)


def test_summarize_missing_admitted_and_first_token():
    """Requests finished without the optional timestamps (older record
    producers): the derived percentiles go NaN, nothing raises."""
    r = _finished(0, 0.0, None, None, 2.0, 4)
    rep = summarize([r])
    assert rep.n_requests == 1
    assert math.isnan(rep.ttft_p50_s)           # no first_token_s
    assert math.isnan(rep.ttft_queue_p50_s)     # no admitted_s
    assert math.isnan(rep.ttft_prefill_p99_s)
    assert math.isnan(rep.decode_tok_s_p50)
    assert rep.latency_p50_s == 2.0             # finish - arrival still real


def test_spec_acceptance_recomputed_from_raw_counters():
    reqs = [_finished(0, 0.0, 0.1, 0.5, 2.0, 8)]
    stats = {"spec_drafted": 10, "spec_accepted": 4,
             "spec_acceptance_rate": 0.99}       # stale copy must lose
    rep = summarize(reqs, stats=stats)
    assert rep.spec_acceptance_rate == pytest.approx(0.4)
    rep0 = summarize(reqs, stats={"spec_drafted": 0, "spec_accepted": 0})
    assert rep0.spec_acceptance_rate == 0.0      # no drafting -> 0, not NaN


def test_report_from_dict_tolerates_old_schema():
    warnings = []

    def warn(msg, **kw):
        warnings.append((msg, kw))

    old = {"pattern": "bursty", "backend": "sim", "n_requests": 4,
           "mystery_field": 1}                   # v0: no schema_version
    rep = report_from_dict(old, source="old.json", warn=warn)
    assert rep.pattern == "bursty" and rep.n_requests == 4
    assert math.isnan(rep.ms_per_token)          # missing float -> NaN
    assert rep.total_tokens == 0                 # missing int -> 0
    msgs = [m for m, _ in warnings]
    assert any("schema mismatch" in m for m in msgs)
    assert any("unknown" in m for m in msgs)
    assert any("missing" in m for m in msgs)

    current = summarize([_finished(0, 0.0, 0.1, 0.5, 2.0, 8)]).to_dict()
    warnings.clear()
    rt = report_from_dict(current, warn=warn)
    assert warnings == []                        # current schema is silent
    assert rt.schema_version == SCHEMA_VERSION


# ----------------------------------------------------------------------------
# percentile nearest-rank boundaries
# ----------------------------------------------------------------------------
def test_percentile_nearest_rank_boundaries():
    assert math.isnan(percentile([], 50))
    assert percentile([7.0], 0) == 7.0
    assert percentile([7.0], 100) == 7.0
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 0) == 1.0      # rank clamps at the first element
    assert percentile(xs, 25) == 1.0     # ceil(0.25*4)=1 -> xs[0]
    assert percentile(xs, 50) == 2.0     # ceil(0.5*4)=2  -> xs[1]
    assert percentile(xs, 75) == 3.0
    assert percentile(xs, 99) == 4.0     # ceil(3.96)=4   -> xs[3]
    assert percentile(xs, 100) == 4.0


# ----------------------------------------------------------------------------
# end-to-end: sim serve under the tracer
# ----------------------------------------------------------------------------
def _sim_backend(slots=4, prompt=64):
    cfg = get_config("llama2-13b")
    w = Workload(cfg, mb=1, ctx=prompt, n_micro=slots)
    env = CostEnv(env_E3(), mbps(200), w)
    return SimBackend(env, n_slots=slots, prompt_tokens=prompt)


def _serve_traced(**cfg_kw):
    arrivals = cli_arrivals("bursty", 6, seed=0, prompt_len=64,
                            max_new_tokens=8, gap_s=4.0, burst_size=4)
    with tracing() as tr:
        sched = ContinuousBatchingScheduler(
            _sim_backend(), SchedulerConfig(**cfg_kw))
        done = sched.serve(requests_from_arrivals(arrivals))
    return done, tr


def test_sim_serve_emits_ordered_lifecycle():
    done, tr = _serve_traced()
    evs = tr.events()
    assert all(not r.rejected for r in done)
    by_track = {}
    for e in evs:
        by_track.setdefault(e[EVT_TRACK], []).append(e)
    for r in done:
        lane = by_track[tr_ev.req_track(r.rid)]
        named = {e[EVT_NAME]: e for e in lane}
        # every lifecycle stage present, once each
        for n in (tr_ev.REQ_ARRIVE, tr_ev.REQ_ADMIT, tr_ev.REQ_QUEUE,
                  tr_ev.REQ_PREFILL, tr_ev.REQ_DECODE, tr_ev.REQ_FINISH,
                  tr_ev.REQ_SPAN):
            assert n in named, (r.rid, n)
        # ordering: arrive <= admit <= finish on the virtual clock
        assert named[tr_ev.REQ_ARRIVE][EVT_TS] == r.arrival_s
        assert named[tr_ev.REQ_ARRIVE][EVT_TS] \
            <= named[tr_ev.REQ_ADMIT][EVT_TS] \
            <= named[tr_ev.REQ_FINISH][EVT_TS]
        # nesting: queue + prefill + decode tile the request span
        span = named[tr_ev.REQ_SPAN]
        q, p, d = (named[tr_ev.REQ_QUEUE], named[tr_ev.REQ_PREFILL],
                   named[tr_ev.REQ_DECODE])
        assert q[EVT_TS] == span[EVT_TS]
        assert q[EVT_TS] + q[EVT_DUR] == pytest.approx(p[EVT_TS])
        assert p[EVT_TS] + p[EVT_DUR] == pytest.approx(d[EVT_TS])
        assert d[EVT_TS] + d[EVT_DUR] == pytest.approx(
            span[EVT_TS] + span[EVT_DUR])
        assert span[EVT_DUR] == pytest.approx(r.finish_s - r.arrival_s)
    # step spans on the pipeline track, in virtual time
    steps = [e for e in evs if e[EVT_NAME] == tr_ev.STEP]
    assert steps and all(e[EVT_PH] == "X" and e[EVT_DUR] > 0 for e in steps)
    # per-stage compute spans landed on device lanes
    assert any(e[EVT_NAME] == tr_ev.STAGE_COMPUTE for e in evs)
    # the whole thing renders in Perfetto
    assert validate_chrome(to_chrome(tr)) == []


def test_sim_serve_paged_emits_kv_counters():
    done, tr = _serve_traced(kv_policy="paged", page_size=16)
    assert all(not r.rejected for r in done)
    names = {e[EVT_NAME] for e in tr.events()}
    assert "kv_pages" in names and "active_requests" in names


def test_disabled_tracer_records_nothing():
    assert get_tracer() is None
    sched = ContinuousBatchingScheduler(_sim_backend(), SchedulerConfig())
    assert sched._tr is None            # zero-cost path: sites see None
    arrivals = cli_arrivals("bursty", 4, seed=0, prompt_len=64,
                            max_new_tokens=4, gap_s=4.0, burst_size=4)
    done = sched.serve(requests_from_arrivals(arrivals))
    assert all(not r.rejected for r in done)


def test_tracer_clock_binds_to_backend_virtual_time():
    """Sim traces carry virtual seconds, not wall time: a sim serve's
    events all live inside the run's virtual span."""
    done, tr = _serve_traced()
    t_hi = max(r.finish_s for r in done)
    for e in tr.events():
        assert -1e-9 <= e[EVT_TS] <= t_hi + 1e-9


def test_engine_fallback_serve_traced():
    """Real-execution path (single-device fallback): the same vocabulary
    renders, with engine.* spans on the pipeline track in wall time."""
    jax = pytest.importorskip("jax")
    from repro.configs.registry import get_smoke_config
    from repro.models import model as M
    from repro.serving import EngineBackend, SamplerConfig

    cfg = get_smoke_config("gemma3-1b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    arrivals = cli_arrivals("bursty", 2, seed=0, prompt_len=8,
                            max_new_tokens=4, gap_s=1.0, burst_size=2)
    with tracing() as tr:
        be = EngineBackend(cfg, params, engine=None, n_slots=2, max_len=32,
                           sampler=SamplerConfig())
        sched = ContinuousBatchingScheduler(be, SchedulerConfig())
        done = sched.serve(
            requests_from_arrivals(arrivals, vocab_size=cfg.vocab_size))
    assert all(not r.rejected for r in done)
    names = {e[EVT_NAME] for e in tr.events()}
    assert tr_ev.ENGINE_PREFILL in names
    assert tr_ev.ENGINE_DECODE in names
    assert tr_ev.REQ_SPAN in names
    assert validate_chrome(to_chrome(tr)) == []


# ----------------------------------------------------------------------------
# one clock with the profiler: clock.sync, profiler annotations, zero cost
# ----------------------------------------------------------------------------
def _syncs(tr):
    return [e for e in tr.events() if e[EVT_NAME] == tr_ev.CLOCK_SYNC]


def test_clock_sync_at_bind_and_after_a_skew(smoke_model):
    """The scheduler's bind pairs the backend clock with time.time_ns;
    EngineBackend.advance_to pairs them again only when it moves the
    skew."""
    import time

    from repro.serving import EngineBackend, SamplerConfig
    cfg, params = smoke_model
    with tracing() as tr:
        be = EngineBackend(cfg, params, engine=None, n_slots=1, max_len=32,
                           sampler=SamplerConfig())
        before = time.time_ns()
        ContinuousBatchingScheduler(be, SchedulerConfig())
        after = time.time_ns()
        (sync,) = _syncs(tr)
        assert before <= sync[EVT_ARGS]["time_ns"] <= after
        assert sync[EVT_TS] == pytest.approx(be.now(), abs=1.0)
        be.advance_to(be.now() - 1.0)             # no skew: no sync
        assert len(_syncs(tr)) == 1
        be.advance_to(be.now() + 5.0)
        syncs = _syncs(tr)
    assert len(syncs) == 2
    # the pair moved by the skew: ring time ran 5 s ahead of wall time
    d_ring = syncs[1][EVT_TS] - syncs[0][EVT_TS]
    d_wall = (syncs[1][EVT_ARGS]["time_ns"]
              - syncs[0][EVT_ARGS]["time_ns"]) * 1e-9
    assert d_ring - d_wall == pytest.approx(5.0, abs=0.05)


def test_sim_sched_steps_trace_in_virtual_time():
    """The simulator's scheduler steps are spans on the virtual clock."""
    done, tr = _serve_traced()
    steps = [e for e in tr.events() if e[EVT_NAME] == tr_ev.SCHED_STEP]
    assert steps and all(e[EVT_PH] == "X" for e in steps)
    t_hi = max(r.finish_s for r in done)
    assert all(0.0 <= e[EVT_TS] <= e[EVT_TS] + e[EVT_DUR] <= t_hi + 1e-9
               for e in steps)
    assert sum(e[EVT_DUR] for e in steps) > 0     # virtual time advanced


def test_span_opens_a_profiler_annotation_only_with_a_tracer(monkeypatch):
    opened = []

    class Ann:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(tr_ev, "_annotation", Ann)
    assert get_tracer() is None
    with tr_ev.span(tr_ev.ENGINE_STEP) as s:
        pass
    assert s is None and opened == []          # the shared no-op context
    with tracing() as tr:
        with tr_ev.span(tr_ev.ENGINE_STEP, track=tr_ev.TRACK_PIPELINE):
            pass
    assert opened == [tr_ev.ENGINE_STEP]
    (e,) = tr.events()
    assert (e[EVT_NAME], e[EVT_PH], e[EVT_TRACK]) == (
        tr_ev.ENGINE_STEP, "X", tr_ev.TRACK_PIPELINE)


def test_untraced_serve_makes_no_tracer_calls(monkeypatch, smoke_model):
    """With no tracer installed, neither the simulator nor the real
    decode path touches the tracer or the profiler."""
    from repro.serving import EngineBackend, SamplerConfig

    calls = []

    def record(name):
        return lambda *a, **k: calls.append(name)
    for meth in ("_push", "span", "clock_sync", "now"):
        monkeypatch.setattr(Tracer, meth, record(meth))
    monkeypatch.setattr(tr_ev, "_annotation", record("annotation"))
    assert get_tracer() is None
    arrivals = cli_arrivals("bursty", 4, seed=0, prompt_len=8,
                            max_new_tokens=3, gap_s=1.0, burst_size=2)
    sched = ContinuousBatchingScheduler(_sim_backend(), SchedulerConfig())
    assert all(not r.rejected
               for r in sched.serve(requests_from_arrivals(arrivals)))
    cfg, params = smoke_model
    be = EngineBackend(cfg, params, engine=None, n_slots=2, max_len=32,
                       sampler=SamplerConfig())
    sched = ContinuousBatchingScheduler(be, SchedulerConfig())
    done = sched.serve(requests_from_arrivals(
        arrivals, vocab_size=cfg.vocab_size))
    assert all(not r.rejected for r in done)
    assert calls == []
