"""The one request generator: a traffic mix file holds only its parameters.

A mix (``chipbench/traffic/<mix>.json``) names ``"generator": "requests"``
and gives::

    {"arrivals": {"process": "backlog", "count": 96}
              or {"process": "gamma", "rate": 4.0, "cv": 1.0},
     "prompt":  {"median": 200, "sigma": 0.8, "min": 64, "max": 1024},
     "output":  {"median": 96, "sigma": 0.6, "min": 32, "max": 256},
     "strata": 8}

``backlog`` queues ``count`` requests at the window's start; ``gamma`` is an
open loop at ``rate`` requests per second whose gaps have the coefficient of
variation ``cv`` (1 is Poisson).  Lengths are lognormal, cut to
``[min, max]``.

The seed changes the token ids, and in an open loop the order of the work,
never the work itself: every seed gets the same set of prompt lengths,
output lengths and gaps.  Lengths are the stratified quantiles of their
distribution; gaps are drawn once from a fixed generator and scaled so that
exactly ``rate * seconds`` requests arrive in ``seconds``.  The sizes are
dealt into rounds of ``strata`` requests, each round holding one length
from every stratum, and shuffled within each round, so any stretch of the
traffic carries the same mix of sizes.  An open loop shuffles by the seed.
A backlog shuffles by a fixed generator: a window works through only its
first requests, so their order is the work, and every seed gets the same.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

#: the fixed generator of the gaps (the same multiset for every seed) and of
#: a backlog's order
GAP_SEED = 20240117


@dataclass
class Arrival:
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # (S,) int32
    max_new: int


def _lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the lognormal cut to [min, max]."""
    dist = NormalDist(math.log(spec["median"]), spec["sigma"])
    lo = dist.cdf(math.log(spec["min"]))
    hi = dist.cdf(math.log(spec["max"]))
    u = lo + (hi - lo) * (np.arange(n) + 0.5) / n
    out = np.array([math.exp(dist.inv_cdf(float(x))) for x in u])
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def _deal(sorted_vals: np.ndarray, strata: int,
          rng: np.random.Generator) -> np.ndarray:
    """Deal sorted values into rounds of ``strata`` (one per stratum) and
    shuffle inside each round."""
    n = len(sorted_vals)
    rounds = -(-n // strata)
    order = []
    for r in range(rounds):
        idx = [s * rounds + r for s in range(strata) if s * rounds + r < n]
        order.extend(rng.permutation(idx).tolist())
    return sorted_vals[np.asarray(order, np.int64)]


def count(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["process"] == "backlog":
        return int(arr["count"])
    return max(1, int(round(arr["rate"] * seconds)))


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Arrival]:
    """The mix's requests for one run, in order of arrival."""
    n = count(mix, seconds)
    strata = int(mix.get("strata", 8))
    rng = np.random.default_rng(seed)
    arr = mix["arrivals"]
    order = (np.random.default_rng(GAP_SEED) if arr["process"] == "backlog"
             else rng)
    prompts = _deal(_lengths(mix["prompt"], n), strata, order)
    outputs = _deal(_lengths(mix["output"], n), strata, order)
    if arr["process"] == "backlog":
        due = np.zeros(n)
    elif arr["process"] == "gamma":
        shape = 1.0 / arr["cv"] ** 2
        gaps = np.sort(np.random.default_rng(GAP_SEED).gamma(shape, 1.0, n))
        gaps *= (n / arr["rate"]) / gaps.sum()
        gaps = _deal(gaps, strata, rng)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return [Arrival(float(t), rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(o))
            for t, p, o in zip(due, prompts, outputs)]
