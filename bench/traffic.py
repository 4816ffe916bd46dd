"""The one traffic generator. A mix is a data file, ``bench/mixes/<name>.json``:

    {"kind": "closed", "depth_ticks": 2, "zipf_a": 1.0, "pool": 16384}
        MLPerf Inference "Offline": before every step the queue is topped up
        to ``depth_ticks`` x slots requests, so every tick is full.
    {"kind": "poisson", "rate_per_s": 1600.0, "zipf_a": 1.0, "pool": 16384}
        MLPerf Inference "Server": open-loop Poisson arrivals at a fixed rate.

Both draw tenants from a Zipf popularity (``zipf_a``) over a seeded rank
shuffle, and cycle through ``pool`` requests made before the window.
"""
from __future__ import annotations

import numpy as np

from deploy import ARRIVALS, rng

KINDS = ("closed", "poisson")


def check_mix(mix: dict) -> dict:
    if mix.get("kind") not in KINDS:
        raise ValueError(f"mix kind must be one of {KINDS}, got "
                         f"{mix.get('kind')!r}")
    if mix["kind"] == "poisson" and not mix.get("rate_per_s", 0) > 0:
        raise ValueError("a poisson mix needs rate_per_s > 0")
    if mix["kind"] == "closed" and not mix.get("depth_ticks", 0) >= 1:
        raise ValueError("a closed mix needs depth_ticks >= 1")
    if not mix.get("pool", 0) >= 1 or not mix.get("zipf_a", 0) > 0:
        raise ValueError("a mix needs pool >= 1 and zipf_a > 0")
    return mix


def zipf_weights(tenants: int, zipf_a: float, seed: int) -> np.ndarray:
    """Tenant popularity proportional to 1/(rank+1)^a over a seeded rank
    shuffle (which tenant is hot differs per seed; the skew shape does
    not). Copied from ``benchmarks/traces.py``."""
    g = np.random.RandomState(int(seed) % 2**32 ^ 0x5EED)
    ranks = g.permutation(tenants)
    w = 1.0 / np.power(ranks + 1.0, zipf_a)
    return w / w.sum()


def arrivals(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson process of
    ``rate_per_s`` over ``[0, seconds)``: exponential gaps, cumulated."""
    g = rng(seed, ARRIVALS)
    n = int(rate_per_s * seconds + 10 * np.sqrt(rate_per_s * seconds) + 16)
    due = np.cumsum(g.exponential(1.0 / rate_per_s, size=n))
    while due[-1] < seconds:  # a tail beyond 10 sigma: draw on
        more = due[-1] + np.cumsum(g.exponential(1.0 / rate_per_s, size=n))
        due = np.concatenate([due, more])
    return due[due < seconds]
