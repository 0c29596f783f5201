"""The one general traffic generator: a traffic file's parameters and a
seed in, a schedule of arrivals out.  No clock, no scheduler, no jax.

Kinds (``"kind"`` in ``perfbench/traffic/<name>.json``):

  closed   ``depth`` pods pending at the window's start and one
           replacement offered for every bind the client sees: a Job
           controller with fixed parallelism.  No schedule: the client
           paces itself on the binds.
  poisson  open loop at ``rate_pods_per_s``.  Every seed gets the SAME
           multiset of inter-arrival gaps -- the N = round(rate * seconds)
           mid-quantiles of the exponential distribution, whose sum is
           the window -- in another order.  So the count, the mean and
           the burstiness of the offered load are the same in every run
           and only the order differs: a run-to-run difference is the
           scheduler's, not the dice's.
  burst    ``poisson`` at ``rate_pods_per_s`` plus ``burst_size`` pods due
           at the same instant every ``burst_every_s`` seconds.

Every kind holds the bound population steady with departures:
``resident_bound`` (see client.py).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np

KINDS = ("closed", "poisson", "burst")


def validate(traffic: Dict[str, Any]) -> None:
    kind = traffic.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r}: not one of {KINDS}")
    if int(traffic.get("resident_bound", 0)) < 1:
        raise ValueError("traffic needs resident_bound >= 1")
    if kind == "closed":
        if int(traffic.get("depth", 0)) < 1:
            raise ValueError("closed traffic needs depth >= 1")
    else:
        if not float(traffic.get("rate_pods_per_s", 0)) > 0:
            raise ValueError(f"{kind} traffic needs rate_pods_per_s > 0")
    if kind == "burst":
        if not (float(traffic.get("burst_every_s", 0)) > 0
                and int(traffic.get("burst_size", 0)) > 0):
            raise ValueError("burst traffic needs burst_every_s and "
                             "burst_size > 0")


def exponential_gaps(rate: float, seconds: float) -> np.ndarray:
    """The fixed multiset of gaps: mid-quantiles of Exp(rate), rescaled
    so that they sum to ``seconds`` exactly."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    return gaps * (seconds / gaps.sum())


def arrivals(traffic: Dict[str, Any], seconds: float, seed: int,
             stream: int = 0) -> np.ndarray:
    """Due times in [0, seconds] of an open-loop kind, sorted.  ``stream``
    picks an independent order of the same gaps (the warm-up uses other
    streams than the window)."""
    kind = traffic["kind"]
    if kind == "closed":
        raise ValueError("closed traffic has no schedule")
    rate = float(traffic["rate_pods_per_s"])
    rng = np.random.default_rng([int(seed), int(stream), 0x7AFF1C])
    due = np.cumsum(rng.permutation(exponential_gaps(rate, seconds)))
    if kind == "burst":
        every = float(traffic["burst_every_s"])
        size = int(traffic["burst_size"])
        k = int(math.floor(seconds / every - 1e-9))
        bursts = np.repeat(every * np.arange(1, k + 1), size)
        due = np.sort(np.concatenate([due, bursts]), kind="stable")
    return due


def pool_size(traffic: Dict[str, Any], seconds: float,
              warm_seconds: float) -> int:
    """How many pod objects to build before the window."""
    if traffic["kind"] == "closed":
        return int(traffic["depth"] + float(traffic["pool_pods_per_s"])
                   * (seconds + warm_seconds))
    return len(arrivals(traffic, seconds + warm_seconds, 0)) + 1024
