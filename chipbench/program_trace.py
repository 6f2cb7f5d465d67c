"""The program's own flight recorder in the traced run, on the profiler's
clock.

The per-layer readers that read the program's spans import this module.
`manifest.resolve` loads per-layer readers only for `--trace 1`, and
before the server is built, so the import installs a
`repro.obs.trace.Tracer` for the traced run alone. The scheduler binds
its clock to the backend's and pairs that clock with `time.time_ns`, the
clock jax.profiler stamps host events with (`clock.sync` events). A
program without `clock.sync` gets no tracer, and `spans()` and
`scopes()` return None there.

Profiler times are seconds since the session's start, which the trace
records (the "Task Environment" plane's `profile_start_time`, read here
when the benchmark loads the trace). A ring time t maps to
  (sync_ns - start_ns) * 1e-9 + (t - sync_t)
from the last `clock.sync` (sync_t, sync_ns) at or before it.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

CAPACITY = 1 << 18
PIPELINE = "pipeline"             # the track of the chip path's spans
TRACER = None                     # the installed Tracer, if any
PROFILE_START_NS: Optional[int] = None


def _install() -> None:
    global TRACER
    try:
        from repro.obs import trace as T
    except ImportError:
        return
    if not hasattr(T, "CLOCK_SYNC"):
        return
    TRACER = T.Tracer(capacity=CAPACITY)
    T.set_tracer(TRACER)


def profile_start_ns(path: str) -> Optional[int]:
    """The session's start on the profiler's clock, from an .xplane.pb."""
    import jax
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            for k, v in plane.stats:
                if k == "profile_start_time":
                    return int(v)
    return None


def _hook_trace_load() -> None:
    """Read the session's start from the trace the benchmark loads."""
    from chipbench import tracereduce as R
    load = R.load_xplane

    def load_xplane(path):
        global PROFILE_START_NS
        PROFILE_START_NS = profile_start_ns(path)
        return load(path)
    R.load_xplane = load_xplane


def to_profiler(events, start_ns: int):
    """A function from ring time to profiler seconds, or None without a
    `clock.sync` in the ring."""
    syncs = sorted((e[2], e[5]["time_ns"]) for e in events
                   if e[0] == "clock.sync")
    if not syncs:
        return None
    ts = [t for t, _ in syncs]

    def at(t: float) -> float:
        i = max(bisect.bisect_right(ts, t) - 1, 0)
        return (syncs[i][1] - start_ns) * 1e-9 + (t - syncs[i][0])
    return at


def spans(tracer=None, start_ns: Optional[int] = None
          ) -> Optional[List[Tuple[str, float, float, dict]]]:
    """The chip path's spans (name, start, end, args) on the profiler's
    clock, sorted by start; None when the program recorded none."""
    tracer = TRACER if tracer is None else tracer
    start_ns = PROFILE_START_NS if start_ns is None else start_ns
    if tracer is None or start_ns is None:
        return None
    evs = tracer.events()
    at = to_profiler(evs, start_ns)
    if at is None:
        return None
    out = [(e[0], at(e[2]), at(e[2]) + e[3], e[5] or {}) for e in evs
           if e[1] == "X" and e[4] == PIPELINE]
    return sorted(out, key=lambda s: s[1]) or None


def scopes(tracer=None) -> Optional[Dict[str, Dict[str, str]]]:
    """{module name: {HLO op name: lime.* part}} from the engine's
    `engine.scopes` events; None when there are none."""
    tracer = TRACER if tracer is None else tracer
    if tracer is None:
        return None
    out: Dict[str, Dict[str, str]] = {}
    for e in tracer.events():
        if e[0] == "engine.scopes":
            out.setdefault(e[5]["module"], {}).update(e[5]["ops"])
    return out or None


_install()
if TRACER is not None:
    _hook_trace_load()
