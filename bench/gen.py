"""The one traffic generator.  A traffic mix is a JSON file of parameters
(``bench/traffic/<mix>.json``); this module turns it and a seed into work.

Two kinds of mix:

- ``train``: packed pre-training rows.  Documents of lognormal length
  (clipped), token ids drawn over the vocabulary slice, packed back to back
  with ``eos_id`` between documents and cut into ``batch`` rows of
  ``seq_len + 1`` tokens (inputs and next-token targets).
- ``serve``: an open-loop request stream.  Arrivals are Poisson (or gamma,
  ``cv`` > 1 for bursts) at the cell's rate; prompt and output lengths are
  lognormal (clipped).  For a given count every seed gets the same multiset
  of inter-arrival gaps, prompt lengths and output lengths -- quantiles of
  those distributions -- in its own order, so seeds change which request
  comes when and what it says, never how much work the window holds.

Every draw comes from ``numpy.random.default_rng`` seeded by the run's seed
(any non-negative integer), so the same seed gives the same inputs.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


def _rng(seed: int, *stream: int):
    return np.random.default_rng([seed, *stream])


def zipf_probs(n: int, a: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** -a
    return p / p.sum()


class IdSampler:
    """Token ids over [lo, vocab): ``"dist": "uniform"``, or ``"zipf"`` with
    exponent ``a``, ranks mapped to ids by a permutation drawn from the
    seed."""

    def __init__(self, spec: dict, vocab: int, seed: int):
        self.lo = int(spec.get("lo", 1))
        n = vocab - self.lo
        dist = spec.get("dist", "zipf")
        if dist not in ("zipf", "uniform"):
            raise ValueError(f"unknown id distribution {dist!r}")
        a = float(spec["a"]) if dist == "zipf" else 0.0
        self.cdf = np.cumsum(zipf_probs(n, a))
        self.perm = _rng(seed, 1).permutation(n) + self.lo

    def __call__(self, rng, size: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(size), side="right")
        return self.perm[np.minimum(r, len(self.perm) - 1)].astype(np.int32)


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles (i + 1/2)/n of a lognormal with the
    given median and sigma, clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def lognormal_draw(spec: dict, rng, size: int) -> np.ndarray:
    v = rng.lognormal(math.log(spec["median"]), spec["sigma"], size)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


# ---------------------------------------------------------------------------
# training rows
# ---------------------------------------------------------------------------
class PackedRows:
    """Batches of packed documents; ``batch(step)`` is a pure function of
    (seed, step)."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.seed = mix, seed
        self.ids = IdSampler(mix["ids"], vocab, seed)

    def batch(self, step: int) -> dict:
        m = self.mix
        B, S = m["batch"], m["seq_len"]
        need = B * (S + 1)
        rng = _rng(self.seed, 2, step)
        # the row starts at a random offset into its first document
        first = int(lognormal_draw(m["doc_len"], rng, 1)[0])
        lens = [int(rng.integers(1, first + 1))]
        while sum(lens) + len(lens) < need:
            lens.append(int(lognormal_draw(m["doc_len"], rng, 1)[0]))
        toks = self.ids(rng, sum(lens) + len(lens))
        ends = np.cumsum(np.asarray(lens) + 1) - 1
        toks[ends] = m["eos_id"]
        rows = toks[:need].reshape(B, S + 1)
        return {"tokens": rows[:, :S], "targets": rows[:, 1:],
                "mask": np.ones((B, S), np.float32)}


# ---------------------------------------------------------------------------
# serving requests
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Req:
    index: int
    due: float                 # seconds after the window opens
    prompt: List[int]
    max_new: int


def request_count(rate: float, seconds: float) -> int:
    return max(int(round(rate * seconds)), 1)


def gaps(arrival: dict, rate: float, n: int) -> np.ndarray:
    """Mid-quantile inter-arrival gaps with mean 1/rate: exponential
    (Poisson arrivals) or gamma with coefficient of variation ``cv``."""
    q = (np.arange(n) + 0.5) / n
    if arrival.get("process", "poisson") == "poisson":
        return -np.log1p(-q) / rate
    cv = float(arrival["cv"])
    shape = 1.0 / (cv * cv)
    from scipy.stats import gamma  # only bursty mixes need it
    return gamma.ppf(q, shape, scale=1.0 / (shape * rate))


def requests(mix: dict, rate: float, seconds: float, vocab: int,
             seed: int, cut: bool = True) -> List[Req]:
    """The requests due in a window of ``seconds`` at ``rate`` per second
    (``cut=False``: all ``request_count`` of them, the last ones possibly
    due after the window)."""
    n = request_count(rate, seconds)
    rng = _rng(seed, 3)
    g = rng.permutation(gaps(mix.get("arrival", {}), rate, n))
    plen = rng.permutation(lognormal_quantiles(mix["prompt_len"], n))
    olen = rng.permutation(lognormal_quantiles(mix["output_len"], n))
    ids = IdSampler(mix["ids"], vocab, seed)
    due = np.cumsum(g) - g[0]
    out = []
    for i in range(n):
        if cut and due[i] >= seconds:
            break
        out.append(Req(i, float(due[i]), ids(rng, int(plen[i])).tolist(),
                       int(olen[i])))
    return out


def engine_shapes(mix: dict):
    """The (bucket, history) prefill shapes this mix can produce, from its
    prompt lengths, chunk size and buckets."""
    e = mix["engine"]
    buckets, chunk = sorted(e["prefill_buckets"]), e["prefill_chunk"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    first = {min(b for b in buckets if b >= min(p, chunk))
             for p in (lo, min(hi, chunk))}
    first |= {b for b in buckets if min(lo, chunk) <= b <= min(hi, chunk)}
    shapes = {(b, False) for b in first}
    if hi > chunk:
        shapes |= {(b, True) for b in buckets}
    return sorted(shapes)
