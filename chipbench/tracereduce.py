"""From a jax.profiler trace to the numbers the per-layer metrics read.

`load_xplane` keeps three kinds of events, in seconds on the profiler's
clock (an op is named by its HLO name, "decode_attention.12", not the
whole instruction text the TPU trace gives it): the operations that ran on each device (the "XLA Ops" line of each
TPU plane), the programs each device executed (its "XLA Modules" line),
and the benchmark's own host spans (TraceAnnotation names starting with
"cb."). Everything after that is plain interval arithmetic on those
lists, kept here so that every change computes the numbers the same way.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # (name, start_s, end_s)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "cb."


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def span(self, name: str) -> List[Event]:
        return [e for e in self.host if e[0] == name]

    def to_json(self) -> str:
        return json.dumps({"ops": {str(k): v for k, v in self.ops.items()},
                           "modules": {str(k): v
                                       for k, v in self.modules.items()},
                           "host": self.host})

    @staticmethod
    def from_json(text: str) -> "Trace":
        d = json.loads(text)
        ev = lambda rows: [(n, float(a), float(b)) for n, a, b in rows]
        return Trace({int(k): ev(v) for k, v in d["ops"].items()},
                     {int(k): ev(v) for k, v in d["modules"].items()},
                     ev(d["host"]))

    def clipped(self, a: float, b: float) -> "Trace":
        """Only the events that overlap [a, b] (for a small recording)."""
        keep = lambda evs: [e for e in evs if e[2] >= a and e[1] <= b]
        return Trace({k: keep(v) for k, v in self.ops.items()},
                     {k: keep(v) for k, v in self.modules.items()},
                     keep(self.host))


def load_xplane(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in (OPS_LINE, MODULES_LINE):
                dest = tr.ops if line.name == OPS_LINE else tr.modules
                dest.setdefault(int(m.group(1)), []).extend(
                    (e.name.split(" = ")[0].lstrip("%"), e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif m is None and plane.name.startswith("/host"):
                tr.host.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for evs in list(tr.ops.values()) + list(tr.modules.values()) + [tr.host]:
        evs.sort(key=lambda e: e[1])
    return tr


def plane_summary(path: str) -> Dict[str, Dict[str, int]]:
    """Event counts by plane and line: what a trace holds, at a glance."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    return {p.name: {ln.name: len(list(ln.events)) for ln in p.lines}
            for p in pd.planes}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of the intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions (each sorted and disjoint)."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], within: Sequence[Interval]) -> List[Interval]:
    """The parts of `within` that `busy` leaves (both unions)."""
    if not within:
        return []
    lo, hi = within[0][0], within[-1][1]
    free, t = [], lo
    for c, d in busy:
        if d <= t:
            continue
        if c >= hi:
            break
        if c > t:
            free.append((t, c))
        t = max(t, d)
    if t < hi:
        free.append((t, hi))
    return intersect(free, within)


def busy(trace: Trace, device: int, within: Sequence[Interval]):
    """Union of the device's op intervals inside `within`."""
    return intersect(union([(a, b) for _, a, b in trace.ops.get(device, [])]),
                     union(within))


def host_names(intervals: Sequence[Interval],
               host: Sequence[Event]) -> List[str]:
    """For each interval, the innermost host span covering its midpoint
    ("no span" where none does). Host spans of one thread nest, so the
    innermost is the covering span that started last: one sweep over the
    midpoints in order, with a heap of started spans."""
    import heapq
    spans = sorted(host, key=lambda e: e[1])
    order = sorted(range(len(intervals)),
                   key=lambda i: intervals[i][0] + intervals[i][1])
    names = ["no span"] * len(intervals)
    heap: list = []                       # (-start, end, name)
    k = 0
    for i in order:
        mid = 0.5 * (intervals[i][0] + intervals[i][1])
        while k < len(spans) and spans[k][1] <= mid:
            heapq.heappush(heap, (-spans[k][1], spans[k][2], spans[k][0]))
            k += 1
        while heap and heap[0][1] < mid:
            heapq.heappop(heap)
        if heap:
            names[i] = heap[0][2]
    return names


def top(pairs, n: int = 10) -> List[List]:
    """Sum values by name, largest first, at most n."""
    acc: Dict[str, float] = {}
    for name, v in pairs:
        acc[name] = acc.get(name, 0.0) + v
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace, within: Sequence[Interval]) -> dict:
    """Device ops that took most time, and idle time by what the host
    was doing, summed over the devices."""
    within = union(within)
    ops, idle = [], []
    for dev, evs in trace.ops.items():
        ops += [(n, length(intersect([(a, b)], within))) for n, a, b in evs
                if b > within[0][0] and a < within[-1][1]]
        free = gaps(busy(trace, dev, within), within)
        idle += [(name, b - a) for name, (a, b)
                 in zip(host_names(free, trace.host), free)]
    return {"device_ops": top(ops), "idle_gaps": top(idle)}
