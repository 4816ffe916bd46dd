"""Process-wide mesh-axis context.

Model code (e.g. the MoE dispatch buffer) occasionally needs
`with_sharding_constraint` hints, but must stay mesh-agnostic and runnable on
a single CPU device. Launchers set the axis names here; when unset, model
code applies no constraints.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


class MeshAxes(NamedTuple):
    dp: tuple | str  # data-parallel axes ("data" or ("pod","data"))
    model: str


_AXES: MeshAxes | None = None
_MESH = None
_GENERATION = 0


def set_mesh_axes(dp, model: str = "model", mesh=None) -> None:
    global _AXES, _MESH, _GENERATION
    _AXES = MeshAxes(dp, model)
    _MESH = mesh
    _GENERATION += 1


def get_mesh():
    return _MESH


def generation() -> int:
    """Monotonic mesh-change counter, bumped by `set_mesh_axes`/`clear`.

    Jitted callers that bake the mesh decision into their trace (the engine's
    `PartitionPlan`, `with_sharding_constraint` hints) thread this as a
    *static* argument — e.g. `hybrid._fused_forward` and the serving
    scheduler's tick — so installing a different mesh keys a fresh
    executable instead of silently replaying the stale one.
    """
    return _GENERATION


def clear() -> None:
    global _AXES, _MESH, _LAYER_CONSTRAINT, _HEAD_CONSTRAINT, _GENERATION
    _AXES = None
    _MESH = None
    _LAYER_CONSTRAINT = None
    _HEAD_CONSTRAINT = None
    _GENERATION += 1


def get() -> MeshAxes | None:
    return _AXES


def constrain(x, *spec) -> jax.Array:
    """Apply a sharding constraint if a mesh context is active.

    spec entries: "dp", "model", or None — translated via the active axes.
    When a mesh was installed with the axes, the constraint is bound to it,
    so it holds outside any ``with mesh:`` block too.
    """
    ax = get()
    if ax is None:
        return x
    resolved = P(*(ax.dp if s == "dp" else (ax.model if s == "model" else None)
                   for s in spec))
    if _MESH is not None:
        resolved = NamedSharding(_MESH, resolved)
    return jax.lax.with_sharding_constraint(x, resolved)


# --- ZeRO-3 gather-at-use -------------------------------------------------
# FSDP-sharded weights must be all-gathered to their TP compute sharding at
# the point of use; left to its own devices GSPMD sometimes resolves the
# dp-axis conflict by all-gathering the *batch* instead (observed on the
# embed/unembed einsums: 8 GB of batch traffic vs 16 MB of weight traffic).
# The step builders register a constraint fn mapping a single layer's param
# subtree to compute shardings; model code applies it at layer entry.

_LAYER_CONSTRAINT = None
_HEAD_CONSTRAINT = None


def set_layer_constraint(fn) -> None:
    global _LAYER_CONSTRAINT
    _LAYER_CONSTRAINT = fn


def set_head_constraint(fn) -> None:
    global _HEAD_CONSTRAINT
    _HEAD_CONSTRAINT = fn


def constrain_layer(layer_params):
    return _LAYER_CONSTRAINT(layer_params) if _LAYER_CONSTRAINT else layer_params


def constrain_head(head_params):
    return _HEAD_CONSTRAINT(head_params) if _HEAD_CONSTRAINT else head_params
