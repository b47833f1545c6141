"""The comparison that decides ``correct``.

Exact checks on every request the run finished: it got exactly its budget
of tokens (no EOS stop is asked for), every token lies in the vocabulary,
and the serving plane recorded no failure. And the logit check on a seeded
sample of the finished greedy requests, the longest among them: the plain
reference (``bench/configs/<reference>.py``, float32 at "highest") runs
once over each prompt with its served tokens, and at every served position
reads how far the served token's logit lies below the reference's best.
The widest such gap over the sample is compared with the cell's limit.

The first served token comes out of the ragged prefill dispatch and the
rest out of the fused decode horizon through the paged cache, so the check
covers both.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def sample(finished: List[dict], seed: int, min_tokens: int,
           max_requests: int) -> List[dict]:
    """Greedy finished requests: the longest, then others in a seeded order
    until ``min_tokens`` served tokens or ``max_requests`` requests."""
    from traffic import rng_for
    greedy = [r for r in finished if r["greedy"]]
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in greedy if r is not longest]
    order = rng_for(seed, 99).permutation(len(rest))
    out, n = [longest], len(longest["tokens"])
    for i in order:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(rest[int(i)])
        n += len(rest[int(i)]["tokens"])
    return out


def padded_len(n: int, chunk: int = 256) -> int:
    return -(-n // chunk) * chunk


class Reference:
    """The reference (float32 at "highest") and its control (the
    configuration's ``control``: the nearest precision below the one it
    states), jitted once per padded length."""

    def __init__(self, ref_mod, config: dict):
        self.ref, self.c = ref_mod, config

    @partial(jax.jit, static_argnums=(0, 4))
    def _stats(self, w, tokens, targets, control: bool):
        if control:
            ctl = self.c["control"]
            return self.ref.logit_stats(
                self.c, w, tokens, targets, dtype=getattr(jnp, ctl["dtype"]),
                precision=jax.lax.Precision[ctl["precision"].upper()])
        return self.ref.logit_stats(self.c, w, tokens, targets)

    def gaps(self, w, reqs: List[dict], length: int,
             control: bool = False) -> Dict[str, float]:
        """Widest gap of the served tokens over ``reqs`` (each with
        ``prompt`` and served ``tokens``) under the weights ``w``, and with
        ``control`` also the widest gap of the token that the control puts
        first at the same positions."""
        worst = {"served": 0.0, "control": 0.0, "positions": 0}
        for r in reqs:
            seq = list(r["prompt"]) + list(r["tokens"][:-1])
            n_p, n_o = len(r["prompt"]), len(r["tokens"])
            if len(seq) > length:
                raise ValueError(f"sequence of {len(seq)} > {length}")
            toks = np.zeros((length,), np.int32)
            toks[:len(seq)] = seq
            tgt = np.zeros((2, length), np.int32)
            pos = np.arange(n_p - 1, n_p - 1 + n_o)
            tgt[0, pos] = r["tokens"]
            if control:
                _, _, arg = self._stats(w, jnp.asarray(toks),
                                        jnp.asarray(tgt), True)
                tgt[1] = np.asarray(arg)
            best, tl, _ = self._stats(w, jnp.asarray(toks),
                                      jnp.asarray(tgt), False)
            best, tl = np.asarray(best)[pos], np.asarray(tl)[:, pos]
            worst["served"] = max(worst["served"], float((best - tl[0]).max()))
            if control:
                worst["control"] = max(worst["control"],
                                       float((best - tl[1]).max()))
            worst["positions"] += n_o
        return worst


def exact_failures(finished: List[dict], vocab: int,
                   unit_failures: int) -> Dict[str, int]:
    """Counts the exact checks compare with 0."""
    return {
        "wrong_token_count": sum(len(r["tokens"]) != r["max_new"]
                                 for r in finished),
        "token_outside_vocab": sum(any(not 0 <= t < vocab
                                       for t in r["tokens"])
                                   for r in finished),
        "plane_failures": unit_failures,
    }


def verdict(checks: Dict[str, dict]) -> Optional[bool]:
    """True where every number compared is within its limit."""
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in checks.values())
