"""The one traffic generator: every mix is a JSON file of parameters.

Sizes and arrivals are stratified: a mix draws the same multiset of
lengths and inter-arrival gaps on every seed (quantiles of the stated
distribution) and the seed only orders them and picks the token ids.
Two seeds then offer the same work, so their runs differ by the system
and not by the draw.  The length arithmetic (lognormal with a mean and a
sigma, clipped to a cap) follows ``repro.sim.workloads``; it is copied
here so that no change to the program moves the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator from the run's seed (any size of integer)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def key_seed(seed: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` from any seed."""
    return int(np.random.SeedSequence([int(seed)]).generate_state(1)[0]
               & 0x7FFFFFFF)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths from ``spec`` as stratified quantiles, unshuffled."""
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, int(spec["value"]))
    elif dist == "choice":
        vals = list(spec["values"])
        out = np.asarray([vals[i % len(vals)] for i in range(n)])
    elif dist == "lognormal":
        sigma = float(spec["sigma"])
        mu = math.log(float(spec["mean"])) - sigma * sigma / 2.0
        z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
        out = np.exp(mu + sigma * z)
        out = np.clip(np.round(out), 1, spec.get("max", np.inf))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    buckets = spec.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets))
        idx = np.minimum(np.searchsorted(b, out), len(b) - 1)
        out = b[idx]
    return out.astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Request:
    uid: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def train_batch(seed: int, step: int, batch: int, seq: int, vocab: int
                ) -> Dict[str, np.ndarray]:
    """Batch ``step`` of a training mix: uniform token ids, every row
    distinct, labels the next token, positions 0..seq-1."""
    ids = rng_for(seed, 1, step).integers(0, vocab, size=(batch, seq + 1),
                                          dtype=np.int32)
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (batch, seq))
    return {"tokens": np.ascontiguousarray(ids[:, :-1]),
            "labels": np.ascontiguousarray(ids[:, 1:]),
            "positions": np.ascontiguousarray(pos)}


def serve_requests(spec: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> List[Request]:
    """The requests of a serving mix for a window of ``seconds``.

    ``arrivals.process``:
    * ``poisson``   -- open loop at ``rate_per_s``: round(rate x seconds)
      requests whose gaps are the exponential's quantiles, shuffled;
    * ``preloaded`` -- ``sessions`` requests all due at 0 (a closed set
      that set-up admits and prefills before the window opens).
    """
    arr = spec["arrivals"]
    if arr["process"] == "poisson":
        rate = float(arr["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-_quantiles(n)) / rate
        gaps = rng_for(seed, 2).permutation(gaps)
        due = np.cumsum(gaps) - gaps[0]
    elif arr["process"] == "preloaded":
        n = int(arr["sessions"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    plen = rng_for(seed, 3).permutation(lengths(spec["prompt"], n))
    olen = rng_for(seed, 4).permutation(lengths(spec["output"], n))
    tok = rng_for(seed, 5)
    return [Request(uid=i, due_s=float(due[i]),
                    prompt=tok.integers(0, vocab, size=int(plen[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(olen[i]))
            for i in range(n)]
