"""Time per decode step that the chip waits on the host, from the
program's own spans (`chipbench/program_trace.py`) mapped onto the
profiler's clock.

Each program `sched.step` span in the traced window that holds an
`engine.decode` span, and whose next `sched.step` holds one too, gives
the interval from its start to the next one's start: one token of every
live request, host work included. The metric is the device-idle time
inside those intervals, per interval, averaged over the cell's chips.
(A decode step followed by an admission or by the client's think time
is left out: that gap is not the host's per-token cost.)

Besides the value, stderr gets the idle time per step split by the
innermost program span it fell in ("no program span" is the benchmark's
own loop between steps), and how far each program `sched.step` start
lies from the benchmark's `cb.sched.step` around the same call.
"""
import bisect
import json
import statistics
import sys

from chipbench import program_trace as P
from chipbench import tracereduce as R

STEP, DECODE = "sched.step", "engine.decode"
OUTSIDE = "no program span"


def decode_intervals(spans, lo: float, hi: float):
    """[start, next start] of each decode step followed by another, both
    starting inside [lo, hi]."""
    steps = [(a, b) for n, a, b, _ in spans if n == STEP]
    dec = sorted(a for n, a, _, _ in spans if n == DECODE)

    def holds(a, b):
        i = bisect.bisect_left(dec, a)
        return i < len(dec) and dec[i] <= b
    out = []
    for (a, b), (c, d) in zip(steps, steps[1:]):
        if lo <= a and c <= hi and holds(a, b) and holds(c, d):
            out.append((a, c))
    return out


def split_at(intervals, cuts):
    """The intervals cut at every point of `cuts` (sorted) inside them."""
    out = []
    for a, b in intervals:
        i = bisect.bisect_right(cuts, a)
        t = a
        while i < len(cuts) and cuts[i] < b:
            out.append((t, cuts[i]))
            t = cuts[i]
            i += 1
        out.append((t, b))
    return out


def idle_split(run, spans):
    """({innermost span: idle s per interval}, number of intervals),
    averaged over the cell's chips."""
    lo, hi = run.traced[0]
    within = decode_intervals(spans, lo, hi)
    if not within:
        return None, 0
    named = [(n, a, b) for n, a, b, _ in spans if b >= lo and a <= hi]
    cuts = sorted({t for _, a, b in named for t in (a, b)})
    acc = {}
    for dev in range(run.chips):
        free = R.gaps(R.busy(run.trace, dev, within), within)
        pieces = split_at(free, cuts)
        for name, (a, b) in zip(R.host_names(pieces, named), pieces):
            name = OUTSIDE if name == "no span" else name
            acc[name] = acc.get(name, 0.0) + (b - a) / len(within) / run.chips
    return acc, len(within)


def clock_offsets_us(run, spans):
    """|program sched.step start - nearest cb.sched.step start|, in us."""
    cb = sorted(a for _, a, _ in run.trace.span("cb.sched.step"))
    lo, hi = run.traced[0]
    out = []
    for n, a, _, _ in spans:
        if n != STEP or not lo <= a <= hi or not cb:
            continue
        i = bisect.bisect_left(cb, a)
        out.append(1e6 * min(abs(cb[j] - a) for j in (i - 1, i)
                             if 0 <= j < len(cb)))
    return sorted(out)


def read(run):
    spans = P.spans()
    if run.trace is None or not run.trace.ops or spans is None:
        return None
    split, n = idle_split(run, spans)
    if split is None:
        return None
    off = clock_offsets_us(run, spans)
    print("chipbench: decode_host_gap_ms split " + json.dumps({
        "steps": n, "ms_per_step": {k: 1e3 * v for k, v in sorted(
            split.items(), key=lambda kv: -kv[1])},
        "clock_offset_us": {"n": len(off),
                            "median": statistics.median(off) if off else None,
                            "max": off[-1] if off else None}}),
        file=sys.stderr)
    return 1e3 * sum(split.values())
