"""The one traffic generator: reads a mix file from ``traffic/`` and a seed.

A mix file holds parameters only (lengths, rates, bursts):

- ``arrival``: inter-arrival gaps, ``{"dist": "gamma", "cv", "rate_per_s"}``
  (cv 1 is Poisson);
- ``prompt`` and ``output``: token counts, ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
- ``lead_in_s``: seconds of schedule before the measured window opens, and
  ``lead_in_burst`` (optional): that many requests due at once when the
  schedule starts, so that over the knee every lane is busy, with a queue
  behind it, before the window opens.

Every size and gap is drawn *stratified*: each block of ``stratum`` draws is
the distribution's ``stratum`` mid-quantiles in an order drawn from the seed
(gaps scaled so that a block's mean is exactly ``1 / rate_per_s``).
Every seed therefore gets the same set of sizes and gaps, in another order,
and the work in a window does not swing with the seed.  Token ids are
uniform over the vocabulary.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float  # seconds after the schedule starts
    prompt: np.ndarray  # int32 token ids
    max_new: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The distribution's ``n`` mid-quantiles ``(j + 0.5) / n``, clipped."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] + 1 - spec["min"])
        vals = np.floor(vals)
    elif dist == "gamma":
        from scipy.special import gammaincinv

        shape = 1.0 / spec["cv"] ** 2
        mean = 1.0 / spec["rate_per_s"]
        vals = gammaincinv(shape, u)
        return vals * (mean / vals.mean())  # gaps in seconds, at the exact rate
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    vals = np.clip(np.rint(vals), spec.get("min", 1), spec.get("max", np.inf))
    return vals.astype(np.int64)


class Strata:
    """Endless stream of stratified draws: blocks of the mid-quantiles,
    each block in its own seeded order."""

    def __init__(self, spec: dict, rng: np.random.Generator):
        self.table = quantiles(spec, int(spec.get("stratum", 16)))
        self.rng = rng
        self.block: list = []

    def __call__(self):
        if not self.block:
            self.block = list(self.table[self.rng.permutation(len(self.table))])
        return self.block.pop()


def schedule(traffic: dict, seed: int, horizon_s: float, vocab: int) -> list[Arrival]:
    """Arrivals due in ``[0, horizon_s)`` for ``seed``; the same seed gives
    the same schedule."""
    streams = {name: np.random.default_rng([seed, i])
               for i, name in enumerate(("gap", "prompt", "output", "tokens"))}
    gap = Strata(traffic["arrival"], streams["gap"])
    plen = Strata(traffic["prompt"], streams["prompt"])
    olen = Strata(traffic["output"], streams["output"])
    toks = streams["tokens"]
    max_prompt = int(traffic["max_prompt"])
    out: list[Arrival] = []
    t = 0.0
    burst = int(traffic.get("lead_in_burst", 0))
    while True:
        if burst:
            burst -= 1
        else:
            t += float(gap())
        if t >= horizon_s:
            return out
        n_out = int(olen())
        prompt = toks.integers(0, vocab, int(plen()), dtype=np.int32)
        out.append(Arrival(t, prompt[:max_prompt], n_out))


def longest_request(traffic: dict) -> int:
    """Most tokens one request can hold: the longest prompt plus output."""
    return int(traffic["max_prompt"]) + int(traffic["output"]["max"])


def round_up(n: int, m: int) -> int:
    return int(math.ceil(n / m) * m)
