"""The one traffic generator: every mix is a data file of parameters.

Sizes and gaps are stratified: for ``n`` requests the generator takes the
distribution's quantiles at ``(i + 0.5) / n`` and lets the seed choose only
their order. Every seed then sends the same multiset of lengths, NFEs and
gaps, so runs with different seeds do the same work in another order, and
warm-up knows every length a run can draw without knowing the seed.

A traffic file holds::

    loop        "open" (arrivals on a schedule) or "closed" (clients that
                send their next request when the last one returns)
    arrival     {"process": "poisson", "rate_per_s": r} or
                {"process": "bursts", "burst": b, "rate_per_s": r}
                (bursts of b requests at one instant, bursts arriving as a
                Poisson process, r the mean request rate); open loop only
    clients     number of clients; closed loop only
    pool        requests a closed loop draws from, in order (cycled)
    seq_len     {"dist": "uniform", "min", "max"} or
                {"dist": "lognormal", "median", "sigma", "min", "max"}
    buckets     the engine's seq_len bucket edges
    solver      solver name; nfe: the NFE set, drawn uniformly
    lead_s      seconds of load before the window opens
    drain_s     seconds a request due in the window may take after it
    check_requests   finished requests the correctness check compares
    schedule_seed    optional: the order of sizes and gaps comes from this
                number and not from the run's seed, so every run replays
                one schedule; the run's seed still draws each request's
                own PRNG seed
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Send:
    at_s: float          # offset from the start of the load (open loop)
    seq_len: int
    nfe: int
    seed: int            # the request's own PRNG seed


def _seq_quantiles(spec: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        v = lo + u * (hi - lo + 1)
        return np.clip(np.floor(v), lo, hi).astype(int)
    if spec["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(v), lo, hi).astype(int)
    raise ValueError(f"unknown seq_len distribution {spec['dist']!r}")


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def sizes(traffic: dict, n: int, rng: np.random.Generator):
    """n (seq_len, nfe) pairs: a fixed multiset of pairs (the i-th length
    quantile with the (i mod k)-th of the k NFEs), in a seed-chosen order."""
    lens = _seq_quantiles(traffic["seq_len"], _strata(n))
    nfes = list(traffic["nfe"])
    pairs = [(int(a), int(nfes[i % len(nfes)])) for i, a in enumerate(lens)]
    return [pairs[i] for i in rng.permutation(n)]


def lengths(traffic: dict, n: int) -> list[int]:
    """Every seq_len that ``n`` requests of this mix can carry (any seed)."""
    return sorted(set(_seq_quantiles(traffic["seq_len"],
                                     _strata(n)).tolist()))


def n_requests(traffic: dict, horizon_s: float) -> int:
    """Requests an open loop schedules over ``horizon_s`` seconds, or the
    pool a closed loop draws from."""
    if traffic["loop"] == "closed":
        return int(traffic["pool"])
    arr = traffic["arrival"]
    burst = arr.get("burst", 1)
    bursts = max(1, math.ceil(arr["rate_per_s"] * horizon_s / burst))
    return bursts * burst


def schedule(traffic: dict, seed: int, horizon_s: float) -> list[Send]:
    """The requests of one run, with their send offsets (open loop) or in
    the order the clients draw them (closed loop, offsets 0)."""
    rng = np.random.default_rng(seed)
    fixed = traffic.get("schedule_seed")
    order = rng if fixed is None else np.random.default_rng(fixed)
    n = n_requests(traffic, horizon_s)
    pairs = sizes(traffic, n, order)
    seeds = rng.integers(0, 2 ** 31 - 1, size=n)
    if traffic["loop"] == "closed":
        at = np.zeros(n)
    else:
        arr = traffic["arrival"]
        burst = arr.get("burst", 1)
        if arr["process"] not in ("poisson", "bursts"):
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        mean_gap = burst / arr["rate_per_s"]
        gaps = order.permutation(-np.log1p(-_strata(n // burst))) * mean_gap
        starts = np.cumsum(gaps) - gaps[0]
        at = np.repeat(starts, burst)
    return [Send(float(t), s, f, int(sd))
            for t, (s, f), sd in zip(at, pairs, seeds)]
