"""Share of the traced time in which the scheduler had live work and no
operation ran on the device: 1 - (union of device op intervals inside the
scheduler's step spans) / (their length), averaged over the cell's chips.
Client think time, when nothing is in flight, is left out."""
from chipbench import tracereduce as R


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    live = R.intersect(R.union([(a, b) for _, a, b in
                                run.trace.span("cb.sched.step")]),
                       run.traced)
    span = R.length(live)
    if span <= 0:
        return None
    busy = [R.length(R.busy(run.trace, dev, live))
            for dev in range(run.chips)]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
