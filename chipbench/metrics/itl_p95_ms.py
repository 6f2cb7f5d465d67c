"""95th percentile of every gap between consecutive output tokens of one
request, pooled over all requests, the later token in the window."""
from chipbench import window as W


def read(run):
    v = W.itls(run.window)
    return 1e3 * W.percentile(v, 95) if v else None
