"""The one traffic generator: turns a mix file (``bench/traffic/<t>.json``)
and a seed into requests.

Every seed gets the same multiset of prompt lengths, output budgets,
sampling kinds and inter-arrival gaps (quantiles of the mix's
distributions), with token ids drawn from the seed. The order of that work
is drawn from the mix's ``order_seed`` where the mix fixes one, and from
the seed otherwise. With a fixed order two seeds do the same work at the
same moments and differ only in token ids, weights and sampled tokens;
without one they differ as two orders of the same work do, which in a
short window decides which requests queue behind each other.

- ``open`` loop: Poisson arrivals at the cell's ``rate_per_s``; a lead-in
  of ``lead_seconds`` before the window, the window, and a tail that keeps
  the load on while the window's last requests finish.
- ``closed`` loop: ``concurrency`` clients; the first requests get budgets
  staggered over the output range, and each completion is replaced at once
  by the next request of the cycle.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Req:
    prompt: List[int]
    max_new: int
    greedy: bool
    due: float = 0.0          # seconds after the schedule's start (open loop)
    phase: str = "window"     # lead | window | tail (open loop)


def _quantiles(d: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles (i + 0.5) / n of the distribution
    ``d``, clipped to [min, max] and rounded."""
    u = (np.arange(n) + 0.5) / n
    if d["dist"] == "uniform":
        x = d["min"] + u * (d["max"] - d["min"] + 1) - 0.5
    elif d["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = d["median"] * np.exp(d["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.rint(x), d["min"], d["max"]).astype(int)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def order_seed(mix: dict, seed: int) -> int:
    """The seed that orders the work: the mix's own where it fixes one."""
    return mix.get("order_seed", seed)


def _batch(mix: dict, n: int, seed: int, stream: int, vocab: int):
    """n requests with the mix's length quantiles and greedy share, in the
    mix's order, with token ids drawn from ``seed``."""
    order = rng_for(order_seed(mix, seed), stream)
    plen = order.permutation(_quantiles(mix["prompt_tokens"], n))
    olen = order.permutation(_quantiles(mix["output_tokens"], n))
    n_greedy = int(round(mix["greedy_share"] * n))
    greedy = order.permutation(np.arange(n) < n_greedy)
    ids = rng_for(seed, stream, 1)
    return [Req(prompt=ids.integers(0, vocab, int(p)).tolist(),
                max_new=int(o), greedy=bool(g))
            for p, o, g in zip(plen, olen, greedy)]


def _gaps(mix: dict, n: int, rate: float, seed: int, stream: int
          ) -> np.ndarray:
    """n exponential inter-arrival gaps at their mid-quantiles, scaled to
    sum to exactly n / rate, in the mix's order."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    g *= (n / rate) / g.sum()
    return rng_for(order_seed(mix, seed), stream).permutation(g)


def open_loop(mix: dict, rate: float, seed: int, seconds: float,
              tail_seconds: float, vocab: int) -> List[Req]:
    """Lead-in, window and tail requests with due times (seconds from the
    schedule's start; the window opens at ``lead_seconds``)."""
    out, t = [], 0.0
    spans = (("lead", mix["lead_seconds"]), ("window", seconds),
             ("tail", tail_seconds))
    for stream, (phase, span) in enumerate(spans):
        n = max(1, int(round(rate * span)))
        reqs = _batch(mix, n, seed, 2 * stream, vocab)
        start = t
        for r, gap in zip(reqs, np.cumsum(_gaps(mix, n, rate, seed,
                                                2 * stream + 1))):
            r.due, r.phase = start + float(gap) - 0.5 * span / n, phase
            out.append(r)
        t = start + span
    return out


class ClosedLoop:
    """The closed loop's request source: the staggered first requests, then
    an endless seeded cycle over the mix's quantiles."""

    def __init__(self, mix: dict, seed: int, vocab: int, cycle: int = 256):
        self.mix, self.seed, self.vocab, self.cycle = mix, seed, vocab, cycle
        self.n = 0
        self._buf: List[Req] = []
        c = mix["concurrency"]
        first = _batch(mix, c, seed, 0, vocab)
        hi = mix["output_tokens"]["max"]
        # remaining budgets spread evenly from short to long, so the first
        # completions come at once and keep coming: no wave of equal ends
        for i, r in enumerate(first):
            r.max_new = int(round(16 + (i + 0.5) / c * (hi - 16)))
        self.first = first

    def next(self) -> Req:
        if not self._buf:
            self._buf = _batch(self.mix, self.cycle, self.seed,
                               1 + self.n // self.cycle, self.vocab)
        self.n += 1
        return self._buf.pop(0)

