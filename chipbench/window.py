"""What the clients saw in the measured window, and the arithmetic of the
end-to-end metrics over it.

Every time is on one clock (the serving backend's, seconds). A token is
emitted when the scheduler step that produced it returns; the window is
[open, close], and the metrics take all the work and all the time in it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class Sent:
    """One request as its client sent it, and what came back."""
    index: int                     # send order; picks deck shape and ids
    client: int
    prompt: np.ndarray
    max_new: int
    send_s: float
    request: object = None         # the scheduler's Request
    token_s: List[float] = field(default_factory=list)
    done_s: Optional[float] = None
    failed: bool = False

    @property
    def admitted_s(self) -> Optional[float]:
        return getattr(self.request, "admitted_s", None)

    @property
    def output(self) -> List[int]:
        return list(getattr(self.request, "output", []))


@dataclass
class Window:
    sent: List[Sent]
    open_s: float
    close_s: float
    # every scheduler step: (t0, t1, "prefill", [prompt lengths admitted])
    # or (t0, t1, "decode", [context of each live request])
    steps: List[tuple] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.close_s - self.open_s

    def inside(self, t: Optional[float]) -> bool:
        return t is not None and self.open_s <= t <= self.close_s


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (as serving/metrics.py computes it); NaN
    for no values."""
    if not values:
        return float("nan")
    s = sorted(values)
    k = max(int(math.ceil(p / 100.0 * len(s))) - 1, 0)
    return float(s[min(k, len(s) - 1)])


def tokens_in(w: Window) -> int:
    return sum(1 for s in w.sent for t in s.token_s if w.inside(t))


def tok_s(w: Window) -> float:
    return tokens_in(w) / w.seconds


def ttfts(w: Window) -> List[float]:
    """Send to first token, for every request whose first token came in
    the window."""
    return [s.token_s[0] - s.send_s for s in w.sent
            if s.token_s and w.inside(s.token_s[0])]


def itls(w: Window) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token came in the window."""
    return [b - a for s in w.sent for a, b in zip(s.token_s, s.token_s[1:])
            if w.inside(b)]


def queue_waits(w: Window) -> List[float]:
    """Send to admission, for every request admitted in the window."""
    return [s.admitted_s - s.send_s for s in w.sent if w.inside(s.admitted_s)]


def attempted(w: Window) -> int:
    return sum(1 for s in w.sent if w.inside(s.send_s))


def failed(w: Window) -> int:
    return sum(1 for s in w.sent if s.failed and w.inside(s.send_s))


def finished(w: Window) -> List[Sent]:
    return [s for s in w.sent if s.done_s is not None and not s.failed
            and w.inside(s.done_s)]
