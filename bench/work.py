"""The least work a serve tick needs, counted from the deployment and the
tick's requests alone: never from the program's arrays, their dtypes or
their padding, so that no way of storing or walking the bank can change it.

For a tick whose filled slots ask tenants t_1 .. t_B (N features, K
templates per class, C_t classes of tenant t):

    ops   = sum_slots 2 * N * K * C_t        each slot compares its features
                                             with its own tenant's templates
    bytes = sum_{distinct t} C_t * K * N / 8  one bit per template element
          + B * N * 4                         the float32 features in

The serve kernel's least time is max(ops / int8 peak, bytes / HBM
bandwidth), with the peaks of `peaks.json`.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def tick_work(cfg: dict, tenants) -> tuple[float, float]:
    """(ops, bytes) of one tick whose filled slots ask ``tenants`` (tenant
    indices, one per filled slot)."""
    n, k, c = (cfg["num_features"], cfg["templates_per_class"],
               cfg["num_classes"])
    ops = 2.0 * n * k * c * len(tenants)
    nbytes = len(set(tenants)) * c * k * n / 8.0 + len(tenants) * n * 4.0
    return ops, nbytes


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def least_seconds(cfg: dict, tenants, peak: dict) -> float:
    """The least time the chip could take for one tick: the larger of its
    operations over the int8 peak and its bytes over HBM bandwidth."""
    ops, nbytes = tick_work(cfg, tenants)
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])
