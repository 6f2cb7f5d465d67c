"""Device time per decode step of the step program's `lime.chunk_params`
part: building the active chunk's layer weights from the resident tier
and the restored streamed layers.

The engine records, once per compiled program, which `lime.*` named
scope each HLO instruction of it belongs to (`engine.scopes`, read
through `chipbench/program_trace.py`). Each device op of the step and
fetch programs executed in the traced window is assigned to the program
execution it ran in; ops that contain other ops (the `while` loops) are
left out, so no time counts twice. The part's time is divided by the
number of step-program executions there, then averaged over the cell's
chips.

Besides the value, stderr gets every part's time per step, the time of
ops outside every part, and the parts' share of the programs' op time.
"""
import json
import sys

from chipbench import program_trace as P
from chipbench import tracereduce as R

PART = "lime.chunk_params"
STEP = "step_fn"                  # InterleavedEngine's step program
OUTSIDE = "no lime scope"


def leaves(ops):
    """The ops that contain no other op. Sorted by start, longest first,
    a container is followed by the first op it holds."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[1] < e[2] and nxt[2] <= e[2])]


def per_step(run, maps, dev):
    """({part: s per step}, step executions) on one device."""
    mods = [(n.split("(")[0], a, b)
            for n, a, b in run.trace.modules.get(dev, [])
            if n.split("(")[0] in maps and R.intersect([(a, b)], run.traced)]
    steps = sum(1 for n, _, _ in mods if STEP in n)
    if not steps:
        return None, 0
    ops = sorted(run.trace.ops.get(dev, []), key=lambda e: e[1])
    acc, i = {}, 0
    for mod, a, b in sorted(mods, key=lambda m: m[1]):
        while i < len(ops) and ops[i][1] < a:
            i += 1
        j = i
        while j < len(ops) and ops[j][1] < b:
            j += 1
        for name, s, e in leaves(ops[i:j]):
            part = maps[mod].get(name, OUTSIDE)
            acc[part] = acc.get(part, 0.0) + (min(e, b) - s) / steps
        i = j
    return acc, steps


def read(run):
    maps = P.scopes()
    if run.trace is None or maps is None:
        return None
    parts, chips = {}, 0
    for dev in range(run.chips):
        acc, steps = per_step(run, maps, dev)
        if not steps:
            continue
        chips += 1
        for k, v in acc.items():
            parts[k] = parts.get(k, 0.0) + v
    if not chips:
        return None
    parts = {k: 1e3 * v / chips for k, v in parts.items()}
    total = sum(parts.values())
    print("chipbench: step parts " + json.dumps({
        "ms_per_step": dict(sorted(parts.items(), key=lambda kv: -kv[1])),
        "scoped_share": (total - parts.get(OUTSIDE, 0.0)) / total
        if total > 0 else None}), file=sys.stderr)
    return parts.get(PART, 0.0)
