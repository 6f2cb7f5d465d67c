"""Median time from a client's send to the scheduler's admission
(Request.admitted_s), over requests admitted in the window."""
from chipbench import window as W


def read(run):
    v = W.queue_waits(run.window)
    return 1e3 * W.percentile(v, 50) if v else None
