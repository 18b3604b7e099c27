"""One generator for every traffic mix; a mix is a JSON file of parameters.

A mix is open: requests arrive at a fixed rate over the window (Poisson
gaps), each due at its arrival whatever the server is doing.  The seed fixes
the order of the work and the token ids, not its amount: the lengths and the
gaps between arrivals are stratified draws of the stated distributions (the
quantiles at (i + 1/2) / n), which the seed shuffles.  So every seed offers
the same prompt tokens, output tokens and arrival gaps, in another order,
and runs with different seeds differ by the order alone.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Job:
    rid: int
    due_s: float                 # seconds after the window opens
    prompt: np.ndarray           # (P,) int32
    max_new: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream per use, fixed by ``seed`` (any
    non-negative whole number, 64 bits and more)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence([seed, *tag]))


def pow2_buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def round_log(x: float, buckets: List[int]) -> int:
    """The bucket nearest to ``x`` in log space."""
    return min(buckets, key=lambda b: (abs(math.log(x / b)), b))


def _u(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of ``dist``."""
    u = _u(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        x = np.clip(x, dist["min"], dist["max"])
        if dist.get("round") == "pow2_log":
            bk = pow2_buckets(dist["min"], dist["max"])
            return np.array([round_log(v, bk) for v in x], np.int64)
        return np.rint(x).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def prompt_buckets(traffic: dict) -> List[int]:
    """Every prompt length the mix can send (the prefill shapes to warm)."""
    d = traffic["prompt_len"]
    if d.get("round") == "pow2_log":
        return pow2_buckets(d["min"], d["max"])
    raise ValueError("prompt lengths must come from a finite set of shapes")


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=n, dtype=np.int64).astype(np.int32)


def open_loop(traffic: dict, seed: int, seconds: float,
              vocab: int) -> List[Job]:
    """Requests due over ``[0, seconds)`` at ``rate_per_s``."""
    rate = traffic["arrivals"]["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, "open")
    gaps = -np.log1p(-_u(n))
    gaps *= seconds / gaps.sum()               # n arrivals over the window
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    prompts = rng.permutation(stratified(traffic["prompt_len"], n))
    outs = rng.permutation(stratified(traffic["output_len"], n))
    return [Job(i, float(due[i]), _tokens(rng, int(prompts[i]), vocab),
                int(outs[i])) for i in range(n)]
