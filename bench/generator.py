"""The one traffic generator: it turns a traffic file's parameters and a
seed into the requests of a run.

Every seed gets the same multiset of sizes and of gaps between arrivals,
in another order: sizes are the quantiles of the stated distribution at
``(i + 0.5) / n`` and gaps those of the exponential, then both are
shuffled by the seed.  So two seeds do the same work, and a seed only
changes which request comes when and which token ids it carries.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass(frozen=True)
class Req:
    id: int
    due_s: float                 # arrival, seconds after the window opens
    prompt: np.ndarray           # (S,) int32 token ids
    max_new_tokens: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use (``stream``) of a run's seed."""
    return np.random.default_rng([int(seed), int(stream)])


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at the quantiles ``(i + 0.5) / n`` of the
    distribution ``spec`` names, truncated to ``[min, max]``: sizes
    outside it are not drawn, rather than piled up at its ends."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        lo, hi = (nd.cdf(np.log(spec[k] / spec["median"]) / spec["sigma"])
                  for k in ("min", "max"))
        z = np.asarray([nd.inv_cdf(lo + float(x) * (hi - lo)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "fixed":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(traffic: dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times of ``n`` requests inside ``[0, seconds)``: exponential
    gaps (Poisson arrivals) at their quantiles, shuffled, then scaled so
    the last request is due half a mean gap before the window closes."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    t = np.cumsum(gaps)
    return t * (seconds * (1.0 - 0.5 / n)) / t[-1]


def requests(traffic: dict, seed: int, seconds: float,
             vocab: int) -> List[Req]:
    """The requests due in a window of ``seconds`` at the traffic's rate,
    in order of due time."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    rng = rng_for(seed, 0)
    prompts = rng.permutation(quantile_sizes(traffic["prompt_tokens"], n))
    outputs = rng.permutation(quantile_sizes(traffic["output_tokens"], n))
    due = arrival_times(traffic, n, seconds, rng)
    return [Req(id=i, due_s=float(due[i]),
                prompt=rng.integers(0, vocab, int(prompts[i]),
                                    dtype=np.int32),
                max_new_tokens=int(outputs[i]))
            for i in range(n)]


def jax_key(seed: int):
    """A JAX key from a seed of any size (seeds above 2**31 included)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)

