"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) holds the parameters:

    {"arrivals": "backlog" | "poisson",
     "rate_rps": requests per second of the window,
     "prompt": {"dist": "lognormal", "median": ..., "sigma": ..., "min": ..., "max": ...},
     "output": {"dist": "lognormal", ...}}

A window of ``seconds`` holds ``ceil(seconds * rate_rps)`` requests.  With
``backlog`` every request is queued at time 0 (an offline job); with
``poisson`` arrivals are open-loop with exponential gaps at ``rate_rps``
on the engine's clock.

Every seed gets the same sizes and gaps in the same order: lengths and
gaps are drawn at stratified quantiles ``(i + 0.5) / n`` of their
distributions and put in order by fixed permutations, and the seed draws
only the token ids.  The engine admits first come, first served under its
one KV clock, so the order of the sizes decides how full the pool stays
(a seeded order moved a backlog's decode steps by 5-10% between seeds);
with the order fixed two seeds differ in content and not in the amount of
work, and the spread between runs is the system's, not the generator's.

A mix file may carry other keys (``source``, ``assumed``): documentation
that the generator does not read.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import List

import numpy as np

#: Fixed streams that order prompt lengths, output lengths and gaps (the
#: same for every seed).
_ORDER_SEED = 20240711


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for any whole-number seed (negative or past 64
    bits included), kept apart per ``salt``."""
    return np.random.default_rng([seed % (1 << 64), seed // (1 << 64) % 2,
                                  *salt])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles of ``spec``, clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(x) for x in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, math.ceil(seconds * mix["rate_rps"]))


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> List[dict]:
    """Requests of one window: dicts with ``rid``, ``prompt`` (int32 ids in
    ``[1, vocab)``), ``max_new_tokens`` and ``arrival_s``, in rid order."""
    n = n_requests(mix, seconds)
    order = np.random.default_rng(_ORDER_SEED)
    prompts = lengths(mix["prompt"], n)[order.permutation(n)]
    outputs = lengths(mix["output"], n)[order.permutation(n)]

    if mix["arrivals"] == "backlog":
        arrivals = np.zeros(n)
    elif mix["arrivals"] == "poisson":
        gaps = -np.log1p(-_quantiles(n)) / mix["rate_rps"]
        gaps *= n / mix["rate_rps"] / gaps.sum()     # mean gap 1 / rate
        arrivals = np.cumsum(gaps[order.permutation(n)])
    else:
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")

    tok = rng_for(seed, 3)
    return [{"rid": i,
             "prompt": tok.integers(1, vocab, size=int(prompts[i]),
                                    dtype=np.int32),
             "max_new_tokens": int(outputs[i]),
             "arrival_s": float(arrivals[i])} for i in range(n)]
