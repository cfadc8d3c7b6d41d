"""The one general traffic generator: a mix is a data file of parameters
(`benchmark/traffic/<name>.json`), and this turns it and the seed into what
the load generator is fed: the order in which rows are sent and, for an open
loop, when each request is due.

Parameters of a mix:
  loop            "closed" (conns x depth requests in flight) or "open"
  conns, depth    connections, and streams in flight on each (closed loop)
  distinct_rows   size of the request table drawn from the configuration
  order           "cycle": rows 0..n-1 over and over (a cyclic order defeats
                  an LRU smaller than n); "zipf": `draws` rows drawn with
                  probability ~ 1/rank**zipf_theta over a seeded ranking
  rate_per_s      open loop: the offered rate, fixed in the file
  warm_s          seconds of the same traffic before the window

Every seed gives the same set of sizes and arrivals in another order: the
gaps of an open loop are the quantiles of the exponential distribution,
shuffled by the seed, so the window of every seed is offered the same
number of requests with the same gaps, and only their order differs.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import numpy as np


def order(mix: Dict[str, Any], seed: int) -> np.ndarray:
    n = int(mix["distinct_rows"])
    if mix["order"] == "cycle":
        return np.arange(n, dtype="<u4")
    if mix["order"] == "zipf":
        rng = np.random.default_rng(seed)
        weights = 1.0 / np.arange(1, n + 1) ** float(mix["zipf_theta"])
        cdf = np.cumsum(weights / weights.sum())
        ranks = np.searchsorted(cdf, rng.random(int(mix["draws"])), side="right")
        return rng.permutation(n)[np.minimum(ranks, n - 1)].astype("<u4")
    raise ValueError(f"unknown order {mix['order']!r}")


def due_times(mix: Dict[str, Any], seed: int, seconds: float) -> Optional[np.ndarray]:
    """Seconds from the generator's start at which each request is due, over
    the warm traffic and the window; None for a closed loop."""
    if mix["loop"] == "closed":
        return None
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rate, warm = float(mix["rate_per_s"]), float(mix["warm_s"])
    rng = np.random.default_rng(seed)

    def part(start: float, length: float) -> np.ndarray:
        """rate x length requests in [start, start + length): the quantiles
        of the exponential gap, in the seed's order, scaled to the length."""
        n = int(math.ceil(rate * length))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        rng.shuffle(gaps)
        at = np.cumsum(gaps)
        return start + at * (length * (n - 0.5) / n / at[-1])

    # the warm traffic and the window each hold their own share, so that the
    # window of every seed is offered the same number of requests
    return np.concatenate([part(0.0, warm), part(warm, seconds)]).astype("<f8")
