"""PartitionPlan: how one matching call maps onto the installed mesh.

PR 3 gave the engine dp-only `shard_map` execution: the batch sharded over
the data-parallel axes, the template bank replicated on every device. That
replication is the engine's biggest scaling assumption — past ~10^5 tenant
class rows the super-bank itself is the memory wall (ROADMAP "Model-parallel
banks"). This module retires it: a `PartitionPlan` is a small hashable value
object, derived *eagerly* from the `EngineConfig`, the mesh in
`repro.distributed.context` and the call's static shapes, that says how a
single matching call executes:

    batch  sharded over the dp axes   (when the batch divides the dp devices)
    bank   class rows sharded over the model axis
           (when C divides the model-axis size and the backend supports it)
    both   2D: each device holds a (B / dp, C / shards) tile of the problem

Bank sharding follows the hardware line's own scaling story (tiling the
analogue template store across 9T4R ACAM units): every device computes
Eq. 8/11 scores and the per-class Eq. 12 partial max on its *class-row
shard*, then one tiny cross-shard `(max, argmax)` reduce over the model axis
recovers the exact global decision — and the windowed winner-vs-runner-up
margin — bit-identically to replicated execution (ties resolve to the lowest
global class index, exactly like `jnp.argmax`).

Because the plan is a NamedTuple of primitives it is hashable, so jitted
callers can treat it (or anything derived from it) as a static argument; and
because it is derived eagerly at the call boundary, installing a different
mesh yields a different plan — paired with `distributed.context.generation()`
as a static arg, callers re-trace instead of replaying a stale layout.

Who consumes the plan:

    repro.match.engine        builds the 2D shard_map specs + reduces from it
    repro.serve.registry      aligns tenant class buckets to shard boundaries
                              (`TemplateBankRegistry(bank_shards=...)`) and
                              places the super-bank on the plan's shardings
                              (`placement_for`)
    repro.serve.scheduler     uploads each tick's operands on the plan's
                              batch sharding (`placement_for`)
    repro.serve.acam_service  infers `bank_shards` via `bank_shards_in_mesh`
    repro.launch.serve        installs the mesh (`--bank-shards`)
"""
from __future__ import annotations

import math
import os
from typing import NamedTuple

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

#: below this many bank shards the sequential all-gather fold wins: the
#: butterfly's log2(S) ppermute rounds cost more launch latency than one
#: all-gather of S tiny rows. At S >= 8 the tree's O(log S) depth takes over.
TREE_REDUCE_MIN_SHARDS = 8

REDUCE_STRATEGIES = ("allgather", "tree")


class PartitionPlan(NamedTuple):
    """How one matching call is partitioned over the installed mesh.

    dp:             mesh axis names the batch is sharded over (() = batch
                    replicated / single device)
    model:          mesh axis name the bank's class rows are sharded over
                    (None = bank replicated on every device)
    dp_devices:     product of the dp axis sizes (1 when unsharded)
    bank_shards:    model-axis size (1 when the bank is replicated)
    rows_per_shard: class rows per bank shard, C // bank_shards (0 when the
                    bank is replicated) — shard s owns global class rows
                    [s * rows_per_shard, (s + 1) * rows_per_shard)
    reduce:         cross-shard reduce strategy over the model axis:
                    "allgather" (gather all S partials, sequential fold) or
                    "tree" (XOR-butterfly ppermute, log2(S) rounds). Both are
                    bit-identical — the merge is associative and f32 max is
                    exact — so this is purely a latency knob
                    (`reduce_strategy`).
    """

    dp: tuple[str, ...] = ()
    model: str | None = None
    dp_devices: int = 1
    bank_shards: int = 1
    rows_per_shard: int = 0
    reduce: str = "allgather"

    @property
    def batch_sharded(self) -> bool:
        return self.dp_devices > 1

    @property
    def bank_sharded(self) -> bool:
        return self.bank_shards > 1

    @property
    def sharded(self) -> bool:
        return self.batch_sharded or self.bank_sharded

    # -- spec builders (the single source of truth for the 2D layout) -------

    def batch_spec(self, rank: int = 1) -> P:
        """Spec for a batch-leading operand/output (rank >= 1)."""
        lead = self.dp if self.dp else None
        return P(lead, *([None] * (rank - 1)))

    def class_spec(self, rank: int = 1) -> P:
        """Spec for a class-row-leading operand (templates, valid)."""
        return P(self.model, *([None] * (rank - 1)))

    def batch_class_spec(self, rank: int = 2) -> P:
        """Spec for a (B, C, ...) output (per_class, scores)."""
        lead = self.dp if self.dp else None
        return P(lead, self.model, *([None] * (rank - 2)))


#: the no-mesh / no-divisibility plan: run the backend directly.
REPLICATED = PartitionPlan()


def reduce_strategy(bank_shards: int) -> str:
    """Pick the cross-shard reduce for a model axis of ``bank_shards``.

    Default: the XOR-butterfly tree when the shard count is a power of two
    at or past `TREE_REDUCE_MIN_SHARDS` (log2(S) hops beat gathering S
    partials), the sequential all-gather fold otherwise. The butterfly
    pairs rank s with s ^ d, so it needs a power-of-two axis.

    ``REPRO_REDUCE_STRATEGY=tree|allgather`` overrides the heuristic —
    "tree" still falls back to all-gather on non-power-of-two axes, where
    the butterfly is undefined.
    """
    pow2 = bank_shards > 1 and (bank_shards & (bank_shards - 1)) == 0
    env = os.environ.get("REPRO_REDUCE_STRATEGY", "").strip().lower()
    if env in REDUCE_STRATEGIES:
        return env if env != "tree" or pow2 else "allgather"
    if pow2 and bank_shards >= TREE_REDUCE_MIN_SHARDS:
        return "tree"
    return "allgather"


def mesh_axes():
    """(mesh, MeshAxes) from the distributed context, or (None, None)."""
    from repro.distributed import context

    mesh = context.get_mesh()
    axes = context.get()
    if mesh is None or axes is None:
        return None, None
    return mesh, axes


def plan_for(*, batch: int, num_classes: int,
             bank_shardable: bool = True) -> tuple[PartitionPlan, object]:
    """Derive the plan for a call with static shapes (batch, num_classes).

    Returns (plan, mesh). Pure and eager — safe at jit trace time (the mesh
    decision is baked into the caller's trace, same contract as
    `distributed.context.constrain`; thread `context.generation()` as a
    static arg to re-trace on mesh changes).

    Rules: the batch shards over the dp axes iff it divides their device
    product; the bank's class rows shard over the model axis iff C divides
    the model-axis size and the backend supports a sharded bank
    (`MatchBackend.supports_bank_sharding` — the device-physics backend
    declines when `sigma_program > 0`, where splitting the programming draw
    would change the realised noise layout vs one physical array).
    """
    mesh, axes = mesh_axes()
    if mesh is None:
        return REPLICATED, None
    dp_axes = axes.dp if isinstance(axes.dp, tuple) else (axes.dp,)
    dp: tuple[str, ...] = ()
    dp_devices = 1
    if all(a in mesh.axis_names for a in dp_axes):
        n = math.prod(mesh.shape[a] for a in dp_axes)
        if n > 1 and batch % n == 0:
            dp, dp_devices = tuple(dp_axes), n
    model = None
    bank_shards = 1
    rows = 0
    if bank_shardable and axes.model in mesh.axis_names:
        s = mesh.shape[axes.model]
        if s > 1 and num_classes % s == 0:
            model, bank_shards, rows = axes.model, s, num_classes // s
    plan = PartitionPlan(dp=dp, model=model, dp_devices=dp_devices,
                         bank_shards=bank_shards, rows_per_shard=rows,
                         reduce=reduce_strategy(bank_shards))
    if not plan.sharded:
        return REPLICATED, None
    return plan, mesh


class Placement(NamedTuple):
    """Where a plan's operands live before the call: the shardings the
    engine's `shard_map` in-specs ask for, so that a jitted caller whose
    arguments already carry them moves nothing between devices.

    bank:   class-row-leading arrays of the super-bank (`class_spec`)
    table:  the per-tenant thresholds table and the bank's shared
            thresholds vector, whole on every device (``P()``)
    batch:  batch-leading operands (`batch_spec`)
    shards: the plan's bank shards (1 when the bank is replicated)
    """

    bank: NamedSharding
    table: NamedSharding
    batch: NamedSharding
    shards: int


def placement_for(*, batch: int, num_classes: int,
                  bank_shardable: bool = True) -> Placement | None:
    """The `Placement` of a call with static shapes (batch, num_classes),
    from the same plan the engine derives (`plan_for`). None when the call
    runs unsharded (no mesh, a one-device mesh, nothing divides): its
    operands then stay uncommitted, on the default device."""
    plan, mesh = plan_for(batch=batch, num_classes=num_classes,
                          bank_shardable=bank_shardable)
    if mesh is None:
        return None
    return Placement(bank=NamedSharding(mesh, plan.class_spec()),
                     table=NamedSharding(mesh, P()),
                     batch=NamedSharding(mesh, plan.batch_spec()),
                     shards=plan.bank_shards)


def bank_shards_in_mesh() -> int:
    """Model-axis size of the installed mesh (1 when none is installed).

    The serving tier uses this to align the registry's class buckets to the
    shard boundaries the engine will cut the super-bank along
    (`TemplateBankRegistry(bank_shards=...)`).
    """
    mesh, axes = mesh_axes()
    if mesh is None or axes.model not in mesh.axis_names:
        return 1
    return int(mesh.shape[axes.model])
