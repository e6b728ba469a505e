"""The one traffic generator: reads a mix's parameters, makes its schedule
from the seed.

A mix is a JSON file under ``bench/traffic/``:

* ``loop``: ``"open"`` (independent users: requests are due on a schedule
  whatever the server does) or ``"closed"`` (a batch job keeps a backlog
  of ``backlog`` requests queued beyond the engine's slots);
* ``rate_per_s`` (open): the mean arrival rate, Poisson;
* ``lead_s``: seconds of load before the measured window opens;
* ``prompt`` / ``output``: lognormal lengths, ``median`` and ``sigma`` of
  the log, clipped to ``[min, max]``;
* ``block``: lengths and gaps are drawn in blocks of this many requests;
* ``order_seed`` (optional): shuffle them with this fixed seed instead of
  the run's.

Every seed gets the same work: within a block, the lengths are their
distribution's quantiles at evenly spaced levels and the inter-arrival
gaps the means of the exponential's equal-probability strata, and the
seed only shuffles them (and draws the prompt tokens).  So runs on
different seeds differ in order, not in how much there is to do.  Where
the order itself moves a metric (an open loop near its knee, whose
first-token tail is set by the particular bursts), the mix fixes it with
``order_seed`` and the seed draws the prompt tokens and the weights.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of a schedule: when it is due (seconds after the load
    starts; 0 for a closed loop), its prompt and its output length."""

    index: int
    due_s: float
    prompt: np.ndarray
    max_new: int


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(seed % 2**64)


def _levels(block: int, quantile) -> np.ndarray:
    """``quantile`` at ``block`` evenly spaced levels."""
    return np.array([quantile((i + 0.5) / block) for i in range(block)])


def _exp_strata(block: int) -> np.ndarray:
    """One block of unit-mean exponential gaps: the mean of each of
    ``block`` equal-probability strata, so a block's mean is exactly 1
    (``G(q) = (1 - q) ln(1 - q) + q`` integrates the quantile)."""
    def G(q: float) -> float:
        return q if q >= 1.0 else (1.0 - q) * math.log1p(-q) + q

    return np.array([(G((i + 1) / block) - G(i / block)) * block for i in range(block)])


def _lognormal(spec: dict, block: int) -> np.ndarray:
    """One block of lognormal lengths clipped to ``[spec.min, spec.max]``."""
    mu, sigma, norm = math.log(spec["median"]), spec["sigma"], NormalDist()
    vals = _levels(block, lambda q: math.exp(mu + sigma * norm.inv_cdf(q)))
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def check_fits(traffic: dict, max_len: int, buckets) -> None:
    """Raise unless every prompt fits a bucket and every request fits the
    context: prompt + output <= ``max_len - 1``."""
    pmax, omax = traffic["prompt"]["max"], traffic["output"]["max"]
    if pmax > max(buckets):
        raise ValueError(f"prompts up to {pmax} exceed the largest bucket {max(buckets)}")
    if pmax + omax > max_len - 1:
        raise ValueError(f"prompt {pmax} + output {omax} exceeds max_len - 1 = {max_len - 1}")


def requests(traffic: dict, seed: int, vocab: int) -> Iterator[Item]:
    """The mix's requests for ``seed``, without end, in blocks."""
    rng = _rng(seed)
    block = traffic["block"]
    plens = _lognormal(traffic["prompt"], block)
    olens = _lognormal(traffic["output"], block)
    if traffic["loop"] == "open":
        rate = traffic["rate_per_s"]
        gaps = _exp_strata(block) / rate
    else:
        gaps = np.zeros(block)
    # "order_seed": the order of lengths and gaps is the mix's own, and the
    # seed draws only the prompt tokens (see the module docstring)
    order = _rng(traffic["order_seed"]) if "order_seed" in traffic else rng
    due, index = 0.0, 0
    while True:
        p, o, g = order.permutation(plens), order.permutation(olens), order.permutation(gaps)
        for i in range(block):
            prompt = rng.integers(0, vocab, int(p[i])).astype(np.int32)
            yield Item(index, due, prompt, int(o[i]))
            due += float(g[i])
            index += 1


def closed_refill(outstanding: int, floor: int) -> int:
    """Requests a closed loop submits now so that ``outstanding`` (queued
    or in a slot) is back at ``floor``."""
    return max(0, floor - outstanding)
