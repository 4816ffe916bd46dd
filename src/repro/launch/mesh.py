"""Mesh construction: every mesh in the repo is built by `make_mesh`.

Functions, not module-level constants, so importing this module never
touches jax device state.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """The one mesh constructor: ``jax.make_mesh`` with every axis
    ``AxisType.Auto``. Since jax 0.8 ``jax.make_mesh`` defaults to Explicit
    axes, under which ``with_sharding_constraint`` and the pallas calls
    inside the engine's ``shard_map`` are refused; the code here places
    arrays through GSPMD and so needs Auto axes. ``devices`` defaults to
    ``jax.devices()``; the first ``prod(shape)`` of them are used."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod mesh: (data=16, model=16); two pods add a leading
    "pod" axis: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests (axes kept for spec parity)."""
    return make_mesh((1, 1), ("data", "model"))


def make_serving_mesh(*, bank_shards: int = 1,
                      axis_names: tuple[str, str] = ("data", "model"),
                      devices=None):
    """(data = devices/bank_shards, model = bank_shards) over the available
    devices — the ACAM serving layout: request batches shard over "data",
    the template super-bank's class rows shard over "model" (the engine's
    `repro.match.plan.PartitionPlan`). ``bank_shards=1`` degenerates to
    pure data parallelism (bank replicated). ``axis_names`` follows a
    `ServiceSpec.mesh` with custom axis names
    (`repro.serve.control.install_mesh` is the usual caller).

    ``devices`` restricts the mesh to an explicit device subset — the
    degraded-fleet path (`HybridService.handle_device_loss` passes the
    survivors after a simulated device failure). Default: all of
    `jax.devices()`.

    On CPU, force host devices first (``REPRO_FORCE_MESH`` /
    `repro.distributed.forcemesh.apply_xla_flags` before jax initialises).
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    ndev = len(devs)
    if bank_shards < 1 or ndev % bank_shards:
        raise ValueError(
            f"bank_shards={bank_shards} must divide the {ndev} available "
            "devices")
    return make_mesh((ndev // bank_shards, bank_shards), axis_names,
                     devices=devs)
