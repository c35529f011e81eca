"""The one traffic generator: reads ``bench/traffic/<name>.json``.

A traffic file holds parameters only.  Two kinds:

``{"kind": "poisson", "rate_per_s": R, "shares": [..] | "equal",
   "arrivals_seed": A, "pool": P}``
    Open loop.  The arrival times are one draw of a Poisson process of
    rate R (exponential gaps of mean 1/R) from the seed A, the same for
    every run's seed.  The run's seed deals the tenants over them, in
    counts split by ``shares``, in its own order.  Arrival times drawn
    anew for every run's seed change the work from seed to seed: on one
    TPU v5e, six seeds of ``tiny3-steady`` read a median latency from
    151 to 256 ms, where two runs of one seed differed by 0 to 16%.

``{"kind": "closed", "outstanding": K, "pool": P}``
    Closed loop.  Every tenant keeps K requests outstanding and submits
    a new one as each completes.

``pool`` is the number of distinct inputs made per tenant; request k of
a tenant uses input ``order[k % pool]`` of a seeded permutation.
"""

from __future__ import annotations

import json
import os
from itertools import combinations

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("poisson", "closed")


def load(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        traffic = json.load(f)
    if traffic.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    if int(traffic.get("pool", 0)) < 1:
        raise ValueError(f"{path}: pool must be >= 1")
    return traffic


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *salt])


def shares(traffic: dict, n_tenants: int) -> np.ndarray:
    s = traffic.get("shares", "equal")
    s = np.full(n_tenants, 1.0) if s == "equal" else np.asarray(s, float)
    if s.shape != (n_tenants,) or np.any(s < 0) or s.sum() <= 0:
        raise ValueError(f"shares {traffic.get('shares')} do not fit "
                         f"{n_tenants} tenants")
    return s / s.sum()


def _tenant_counts(n: int, share: np.ndarray) -> np.ndarray:
    """Largest-remainder split of n requests by share."""
    raw = n * share
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def open_schedule(traffic: dict, n_tenants: int, seconds: float,
                  seed: int) -> list:
    """[(due seconds from the window's start, tenant)], sorted by due."""
    rng = _rng(int(traffic["arrivals_seed"]), 0)
    mean_gap = 1.0 / float(traffic["rate_per_s"])
    due = []
    t = rng.exponential(mean_gap)
    while t < seconds:
        due.append(float(t))
        t += rng.exponential(mean_gap)
    counts = _tenant_counts(len(due), shares(traffic, n_tenants))
    tenants = _rng(seed, 1).permutation(
        np.repeat(np.arange(n_tenants), counts))
    return [(d, int(k)) for d, k in zip(due, tenants)]


def input_order(traffic: dict, n_tenants: int, seed: int) -> list:
    """Per tenant, the seeded order in which its pooled inputs are used."""
    pool = int(traffic["pool"])
    return [_rng(seed, 2, t).permutation(pool) for t in range(n_tenants)]


def occupancies(traffic: dict, n_tenants: int) -> list:
    """Every tenant set a round can serve under this traffic, largest
    first.  A closed loop keeps every queue non-empty, so only the full
    house; an open loop can leave any queue empty."""
    everyone = list(range(n_tenants))
    if traffic["kind"] == "closed":
        return [everyone]
    live = [t for t, s in enumerate(shares(traffic, n_tenants)) if s > 0]
    return [list(c) for k in range(len(live), 0, -1)
            for c in combinations(live, k)]
