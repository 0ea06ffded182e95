"""One general generator for every traffic mix (``chipbench/traffic/*.json``).

The distributions follow the program's seeded generators (lognormal
and uniform lengths, Poisson arrivals, Zipf token ids) but are kept
here, so a change to the program cannot move the yardstick.  Arrival
times are wall-clock offsets into the measured window.

Every seed gets the same work in the same order: the lengths and
inter-arrival gaps of a window are stratified quantiles of their
distributions, shuffled once by a fixed stream, and the seed draws only
the token ids.  Two seeds then differ in content, not in the schedule:
at four fifths of the knee a reordering alone moves the tail of the
first-token wait by half, which would hide any change a later PR makes.

A mix file holds:

* ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop; the
  window holds ``round(r * seconds)`` requests) or
  ``{"kind": "saturated"}`` (the harness keeps ``max_batch`` requests
  queued; requests come from a pool of ``pool`` entries);
* ``prompt_len`` / ``output_len``: ``{"kind": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"kind": "uniform", "min", "max"}``;
  ``buckets`` on the prompt rounds each length up to the next bucket;
* ``tokens``: ``{"zipf_a": a}``, ranks mapped through a seeded
  permutation of the vocabulary;
* ``max_batch``, ``max_seq``, ``drain_limit_s``, ``warm_new_tokens``.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

_N01 = NormalDist()


@dataclasses.dataclass(frozen=True)
class Req:
    due_s: float          # offset into the window (saturated: 0)
    prompt: np.ndarray    # [S] int32
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Lengths at quantiles ``u`` of a length distribution."""
    lo, hi = spec["min"], spec["max"]
    if spec["kind"] == "lognormal":
        z = np.array([_N01.inv_cdf(float(x)) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        out = np.clip(np.round(x), lo, hi)
    elif spec["kind"] == "uniform":
        out = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length kind {spec['kind']!r}")
    out = out.astype(np.int64)
    buckets = spec.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets))
        out = b[np.searchsorted(b, out)]
    return out


class Traffic:
    """The requests of one mix for one seed."""

    def __init__(self, spec: dict, vocab: int, seed: int):
        self.spec = spec
        self.vocab = vocab
        self.rng = np.random.default_rng(int(seed) % 2**64)
        self.order = np.random.default_rng(0)     # the schedule: no seed
        self.perm = self.rng.permutation(vocab).astype(np.int32)
        self.max_batch = spec["max_batch"]
        self.max_seq = spec["max_seq"]
        self.saturated = spec["arrivals"]["kind"] == "saturated"
        self._pool: List[Req] = []
        self._next = 0

    def _prompt(self, n: int) -> np.ndarray:
        ranks = self.rng.zipf(self.spec["tokens"]["zipf_a"], size=n)
        return self.perm[(ranks - 1) % self.vocab]

    def _sized(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        u = _quantiles(n)
        p = lengths(self.spec["prompt_len"], self.order.permutation(u))
        o = lengths(self.spec["output_len"], self.order.permutation(u))
        return p, o

    def buckets(self) -> List[int]:
        return sorted(self.spec["prompt_len"]["buckets"])

    def window(self, seconds: float) -> List[Req]:
        """Open loop: the requests due in a window of ``seconds``."""
        rate = self.spec["arrivals"]["rate_per_s"]
        n = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-self.order.permutation(_quantiles(n))) / rate
        due = seconds * np.cumsum(gaps) / (gaps.sum() + gaps.mean())
        p, o = self._sized(n)
        return [Req(float(t), self._prompt(int(pl)), int(ol))
                for t, pl, ol in zip(due, p, o)]

    def next_saturated(self) -> Req:
        """Saturated: the next request of a seeded pool, cycled."""
        if not self._pool:
            p, o = self._sized(self.spec["arrivals"]["pool"])
            self._pool = [Req(0.0, self._prompt(int(pl)), int(ol))
                          for pl, ol in zip(p, o)]
        r = self._pool[self._next % len(self._pool)]
        self._next += 1
        return r

    def warm(self) -> List[Req]:
        """Set-up traffic: every prompt bucket, and enough requests to
        fill every slot once, each with a short output."""
        b = self.buckets()
        n = max(self.max_batch, len(b))
        k = self.spec["warm_new_tokens"]
        return [Req(0.0, self._prompt(b[i % len(b)]), k) for i in range(n)]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (nearest rank), of at least one value."""
    v = sorted(values)
    i = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[i])
