"""Median time from a client's send to its first token, over every
request whose first token came in the window."""
from chipbench import window as W


def read(run):
    v = W.ttfts(run.window)
    return 1e3 * W.percentile(v, 50) if v else None
