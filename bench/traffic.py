"""Request streams drawn from a traffic file and a seed.

One general generator reads every mix.  A traffic file gives the loop
(``open``: arrivals on a Poisson schedule in seconds, or ``closed``:
``clients`` that each send their next request when the last one
completes), lognormal prompt and output lengths (median, sigma, low,
high), and ``strata``.

Lengths and gaps are stratified so that every seed does the same work:
each consecutive block of ``strata`` requests takes the same
``strata`` quantiles of each distribution, and the seed only permutes
them within the block (prompt lengths, output lengths and gaps each
independently).  Token ids are uniform over the vocabulary.  The
lognormal arithmetic (``exp(N(log(median), sigma))``, rounded, clipped)
follows the program's own ``LengthDist``; the quantiles replace its
random draws.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, Iterator, List

import numpy as np

LOOPS = ("open", "closed")


@dataclass
class Request:
    prompt: List[int]
    max_new: int
    gap_s: float          # open loop: seconds after the previous arrival


def lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a clipped, rounded lognormal length."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    draws = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(draws).astype(np.int64), spec["low"],
                   spec["high"])


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    """Mid-quantiles of Exp(rate), scaled so the block spans exactly
    ``n / rate`` seconds."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q / q.sum() * (n / rate)


def validate(t: Dict) -> None:
    if t["loop"] not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}, got {t['loop']!r}")
    if t["loop"] == "open" and not t.get("rate_req_s", 0) > 0:
        raise ValueError("an open loop needs rate_req_s > 0")
    if t["loop"] == "closed" and not t.get("clients", 0) >= 1:
        raise ValueError("a closed loop needs clients >= 1")
    for key in ("prompt", "output"):
        d = t[key]
        if not 1 <= d["low"] <= d["median"] <= d["high"]:
            raise ValueError(f"{key}: need 1 <= low <= median <= high")
    need = t["prompt"]["high"] + t["output"]["high"]
    if need > t["engine"]["cache_len"]:
        raise ValueError(f"longest request needs {need} KV positions, "
                         f"cache_len is {t['engine']['cache_len']}")


def stream(t: Dict, vocab: int, seed: int) -> Iterator[Request]:
    """Endless request stream of traffic file ``t`` for ``seed``."""
    validate(t)
    m = int(t["strata"])
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    prompts = lognormal_quantiles(t["prompt"], m)
    outputs = lognormal_quantiles(t["output"], m)
    gaps = (exponential_quantiles(t["rate_req_s"], m)
            if t["loop"] == "open" else np.zeros(m))
    while True:
        p, o, g = (rng.permutation(prompts), rng.permutation(outputs),
                   rng.permutation(gaps))
        for i in range(m):
            ids = rng.integers(0, vocab, size=int(p[i]))
            yield Request(ids.tolist(), int(o[i]), float(g[i]))
