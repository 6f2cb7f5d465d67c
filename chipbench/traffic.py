"""The general traffic generator: a traffic file's parameters -> a deck.

A deck is the fixed list of request shapes a run draws from, in order,
cycling: each prompt length appears in exact proportion to its weight,
and output lengths and think times are the deck's stratified quantiles
of their distributions, shuffled once. The order is the same for every
seed: a window spans about one and a half decks, so a seeded order would
change which shapes it holds and with them the work measured. The seed
draws the token ids (and the weights).

Traffic file keys:
  client        client kind (clients/<kind>.py)
  pattern       the server's LIME pattern: "sporadic" (one micro-batch)
                or "bursty" (a micro-batch per stage)
  clients       number of closed-loop clients
  deck          requests per deck
  prompt_len    {"values": [...], "weights": [...]} (weights x deck whole)
  output_len    {"median", "sigma", "min", "max"}: lognormal, clipped
  think_s       {"mean"}: exponential think time (0 for none)
  ramp_requests completions before the window opens (the queue's warm-up)
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass(frozen=True)
class Shape:
    prompt_len: int
    max_new: int


ORDER_SEED = 12            # the deck's one shuffle, the same in every run


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def prompt_lengths(traffic: dict) -> List[int]:
    return sorted(traffic["prompt_len"]["values"])


def max_len(traffic: dict) -> int:
    """The per-slot cache the mix needs: its longest prompt and output."""
    return max(traffic["prompt_len"]["values"]) \
        + traffic["output_len"]["max"]


def _counts(traffic: dict) -> List[int]:
    n = traffic["deck"]
    pl = traffic["prompt_len"]
    counts = [w * n for w in pl["weights"]]
    if any(abs(c - round(c)) > 1e-9 for c in counts) \
            or round(sum(counts)) != n:
        raise ValueError(f"prompt weights {pl['weights']} x deck {n} are "
                         f"not whole counts summing to the deck")
    return [int(round(c)) for c in counts]


def deck(traffic: dict) -> List[Shape]:
    n = traffic["deck"]
    prompts = [v for v, c in zip(traffic["prompt_len"]["values"],
                                 _counts(traffic)) for _ in range(c)]
    ol = traffic["output_len"]
    dist = statistics.NormalDist()
    outs = [int(min(max(round(ol["median"] * math.exp(
        ol["sigma"] * dist.inv_cdf((i + 0.5) / n))), ol["min"]), ol["max"]))
        for i in range(n)]
    rng = _rng(ORDER_SEED, 1)
    prompts = [prompts[i] for i in rng.permutation(n)]
    outs = [outs[i] for i in rng.permutation(n)]
    return [Shape(p, o) for p, o in zip(prompts, outs)]


def think_times(traffic: dict) -> List[float]:
    """Exponential think times at the deck's stratified quantiles, in the
    deck's fixed order."""
    n = traffic["deck"]
    mean = traffic.get("think_s", {}).get("mean", 0.0)
    t = [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    return [t[i] for i in _rng(ORDER_SEED, 2).permutation(n)]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> np.ndarray:
    """The token ids of the index-th request sent: uniform over the real
    vocabulary, never 0 (the program's pad id)."""
    return _rng(seed, 1000 + index).integers(1, vocab, length,
                                             dtype=np.int32)
