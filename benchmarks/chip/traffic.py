"""The one generator of request streams: a mix is a data file of parameters.

A length distribution is a dict: ``{"dist": "lognormal", "median": m,
"sigma": s, "min": a, "max": b}`` or ``{"dist": "uniform", "min": a,
"max": b}``.  Requests come in blocks of ``block`` requests whose lengths
are the distribution's ``block`` strata midpoints (the same multiset in
every block, for every seed); the seed only orders them, and pairs prompt
with output lengths.  So every seed offers the same work in another order,
and a backlog never runs dry: blocks follow one another without end.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, Iterator, List, Tuple

import numpy as np


def strata(dist: Dict, n: int) -> List[int]:
    """The ``n`` strata midpoints of a clipped length distribution."""
    qs = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        mu = math.log(dist["median"])
        vals = [math.exp(mu + dist["sigma"] * NormalDist().inv_cdf(q))
                for q in qs]
    elif dist["dist"] == "uniform":
        vals = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return [int(min(max(round(v), dist["min"]), dist["max"])) for v in vals]


def requests(mix: Dict, seed: int) -> Iterator[Tuple[int, int]]:
    """Endless (prompt_len, max_new_tokens) pairs for this seed."""
    n = int(mix["block"])
    prompts = np.asarray(strata(mix["prompt"], n))
    outputs = np.asarray(strata(mix["output"], n))
    block = 0
    while True:
        rng = np.random.default_rng([int(seed) % (1 << 63), 7, block])
        for p, o in zip(rng.permutation(prompts), rng.permutation(outputs)):
            yield int(p), int(o)
        block += 1
