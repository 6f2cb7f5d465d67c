"""Closed-loop clients: each sends its next request only when its last
one has finished, after a think time. A slow server receives less load
and the queue stays bounded by the number of clients.

`make(traffic)` returns the client set `run.drive` uses:
  start(t)            -> [(send_time, client), ...] for the first sends
  finished(client, t) -> the client's next send, as a list
"""
from __future__ import annotations

from typing import List, Tuple

from chipbench.traffic import think_times


class ClosedLoop:
    def __init__(self, n_clients: int, thinks: List[float]):
        self.n = n_clients
        self._thinks = thinks
        self._k = 0

    def start(self, t: float) -> List[Tuple[float, int]]:
        return [(t, c) for c in range(self.n)]

    def finished(self, client: int, t: float) -> List[Tuple[float, int]]:
        think = self._thinks[self._k % len(self._thinks)]
        self._k += 1
        return [(t + think, client)]


def make(traffic: dict) -> ClosedLoop:
    return ClosedLoop(traffic["clients"], think_times(traffic))
