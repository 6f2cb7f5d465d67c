"""Device time per decode step: the engine's weight-fetch program plus
its step program, summed over their executions in the traced window, per
execution of the step program, averaged over the cell's chips."""
from chipbench import tracereduce as R

STEP = "step_fn"           # InterleavedEngine's jitted shard_map bodies
FETCH = "fetch_fn"


def read(run):
    if run.trace is None:
        return None
    per_dev = []
    for dev in range(run.chips):
        mods = [(n, a, b) for n, a, b in run.trace.modules.get(dev, [])
                if R.intersect([(a, b)], run.traced)]
        steps = [m for m in mods if STEP in m[0] and FETCH not in m[0]]
        if not steps:
            continue
        t = sum(b - a for n, a, b in mods if STEP in n or FETCH in n)
        per_dev.append(t / len(steps))
    return 1e3 * sum(per_dev) / len(per_dev) if per_dev else None
