"""The matching engine: one API over the backend registry + mesh sharding.

`MatchEngine` is the single entry point every production caller
(`repro.core.hybrid`, `repro.serve.*`, `repro.launch.serve`, the
benchmarks and examples) routes Eq. 8-12 template matching through:

    eng = engine_for(method="feature_count", backend="kernel")
    pred, per_class = eng.classify_features(features, bank)

Construction is cheap and memoised per `EngineConfig` (`engine_for`), and
every method is safe to call at jit trace time: backend resolution is a
pure dict lookup, block resolution is the `repro.kernels.tuning` cached
lookup, and the "auto" policy decides reference-vs-kernel from static
shapes only.

Backend defaults
----------------
The process default backend (what `backend=None` / an omitted engine
backend resolves to) is ``REPRO_MATCHING_BACKEND`` at import, "auto"
otherwise. `set_default_backend` changes it; `use_backend("...")` scopes a
change to a `with` block (tests / env parity). Unlike the old
`repro.core.matching._backend` global, the default is only ever read
*eagerly at the caller boundary* — jitted callers receive the backend as a
static argument (`hybrid._fused_forward`, the scheduler tick), so changing
the default triggers a fresh trace instead of being silently baked into an
existing executable.

Mesh sharding (the PartitionPlan layer)
---------------------------------------
When `repro.distributed.context` holds a mesh (set by a launcher), every
call derives a `repro.match.plan.PartitionPlan` from its static shapes and
executes under a plan-driven 2D `jax.shard_map`:

  * the **batch** shards over the data-parallel axes (when it divides the
    dp device count) — embarrassingly parallel, as in PR 3;
  * the **bank's class rows** shard over the model axis (when C divides the
    model-axis size and the backend supports it): each device runs the
    backend's (fused) classify on its class-row shard, producing per-class
    partials, and one tiny cross-shard ``(max, argmax)`` reduce over the
    model axis recovers the exact global Eq. 12 decision — and the windowed
    winner-vs-runner-up margin — **bit-identically** to replicated
    execution (ties resolve to the lowest global class index, exactly like
    `jnp.argmax`; see `_reduce_winner` / `_reduce_margin`).

Callers that jit around the engine bake the plan into their trace;
launchers must install the mesh before the first call (the same contract
as `context.constrain`), and jitted callers thread
`distributed.context.generation()` as a static argument so installing a
*different* mesh re-traces instead of silently replaying the old layout.
"""
from __future__ import annotations

import contextlib
import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.templates import TemplateBank
from repro.match import backends as backends_lib
from repro.match import plan as plan_lib
from repro.match.backends import backend_for, backend_names, tiny_cutoff
from repro.match.config import EngineConfig, validate
from repro.match.plan import PartitionPlan, plan_for

Array = jax.Array

_default_backend = os.environ.get("REPRO_MATCHING_BACKEND", "auto")


def default_backend() -> str:
    """The process default backend name ("auto" unless overridden)."""
    return _default_backend


def set_default_backend(name: str) -> None:
    """Set the process default backend ("auto" or any registered name).

    Read eagerly by callers (never inside traced code): jitted entry points
    take the resolved name as a static argument, so a change here produces
    a new trace on the next call rather than mutating a compiled one.
    """
    global _default_backend
    if name != "auto" and name not in backend_names():
        raise ValueError(f"unknown matching backend {name!r}; use "
                         f"{('auto',) + backend_names()}")
    _default_backend = name


@contextlib.contextmanager
def use_backend(name: str):
    """Scope the default backend to a `with` block (tests / env parity)."""
    prev = default_backend()
    set_default_backend(name)
    try:
        yield
    finally:
        set_default_backend(prev)


# ---------------------------------------------------------------------------
# Mesh plumbing
# ---------------------------------------------------------------------------

def dp_axes_in_mesh():
    """(mesh, dp_axes) from the distributed context, or (None, None) when
    no usable data-parallel mesh is installed."""
    mesh, axes = plan_lib.mesh_axes()
    if mesh is None:
        return None, None
    dp = axes.dp if isinstance(axes.dp, tuple) else (axes.dp,)
    if any(a not in mesh.axis_names for a in dp):
        return None, None
    if math.prod(mesh.shape[a] for a in dp) <= 1:
        return None, None
    return mesh, dp


def batch_specs(dp, n_batch_args: int, out_ranks: tuple[int, ...]):
    """shard_map specs for a dp-only matching call: batch-leading operands
    sharded over the dp axes, the bank replicated, outputs batch-leading.

    Exposed for tests: the first `n_batch_args` in_specs carry P(dp) — the
    queries ARE dp-sharded — and the bank spec is P(). Bank-sharded calls
    derive their 2D specs from the `PartitionPlan` instead
    (`plan.batch_spec` / `plan.class_spec` / `plan.batch_class_spec`).
    """
    in_specs = tuple(P(dp) for _ in range(n_batch_args)) + (P(),)
    out_specs = tuple(P(dp, *([None] * (r - 1))) for r in out_ranks)
    return in_specs, out_specs


def bank_specs(plan: PartitionPlan) -> TemplateBank:
    """shard_map in_specs for a `TemplateBank` under the plan: class-row
    leading arrays cut over the model axis, thresholds replicated."""
    row = plan.class_spec()
    return TemplateBank(templates=row, lower=row, upper=row, valid=row,
                        thresholds=P())


# ---------------------------------------------------------------------------
# Cross-shard reduces (the "one tiny argmax reduce" of a bank-sharded call)
# ---------------------------------------------------------------------------
#
# Each model-axis shard contributes a (top1, global winner index[, top2])
# summary of its class rows. Shards hold *disjoint* class-index ranges, so
# the merge below is exact: the winner is the lexicographic max on
# (score desc, index asc) — precisely `jnp.argmax`'s lowest-index tie rule —
# and the global runner-up over "all classes except the winner's position"
# is max(loser shards' top1, winner shard's top2).
#
# Two strategies, both bit-identical (`plan.reduce` picks one):
#
#   allgather  gather all S partials ((S, B) scalars), fold sequentially —
#              one collective, O(S) merge depth. The PR-6 behaviour.
#   tree       XOR-butterfly: log2(S) `ppermute` rounds, each merging a
#              partial from rank s ^ d. After round log2(S) every rank holds
#              the identical global result. Exactness: the merge is
#              associative + commutative on disjoint index sets (f32 max is
#              exact, the lexicographic tie rule is order-free), so ANY
#              reduction tree yields the same bits as the sequential fold.
#              Requires a power-of-two axis (`plan.reduce_strategy` only
#              selects it there).

def _merge_winner(at, ai, bt, bi):
    take = (bt > at) | ((bt == at) & (bi < ai))
    return jnp.where(take, bt, at), jnp.where(take, bi, ai)


def _merge_margin(at, ai, ar, bt, bi, br):
    take = (bt > at) | ((bt == at) & (bi < ai))
    # new runner-up: the losing side's top1 joins the candidate set
    r = jnp.where(take, jnp.maximum(br, at), jnp.maximum(ar, bt))
    return jnp.where(take, bt, at), jnp.where(take, bi, ai), r


def _butterfly(parts: tuple, merge, axis: str, num_shards: int) -> tuple:
    """XOR-butterfly all-reduce of per-shard partials under ``merge``."""
    d = 1
    while d < num_shards:
        perm = [(s, s ^ d) for s in range(num_shards)]
        peer = tuple(jax.lax.ppermute(p, axis, perm) for p in parts)
        parts = merge(*parts, *peer)
        d *= 2
    return parts


def _reduce_winner(top1: Array, gidx: Array, axis: str, num_shards: int,
                   strategy: str = "allgather") -> tuple[Array, Array]:
    if strategy == "tree":
        return _butterfly((top1, gidx), _merge_winner, axis, num_shards)
    t = jax.lax.all_gather(top1, axis)  # (S, B)
    i = jax.lax.all_gather(gidx, axis)
    best_t, best_i = t[0], i[0]
    for s in range(1, num_shards):
        best_t, best_i = _merge_winner(best_t, best_i, t[s], i[s])
    return best_t, best_i


def _reduce_margin(top1: Array, gidx: Array, top2: Array, axis: str,
                   num_shards: int, cap: float,
                   strategy: str = "allgather") -> tuple[Array, Array]:
    """Combine windowed margin partials -> (pred, margin), matching
    `repro.kernels.layout.windowed_margin` bit for bit (same clamp, same
    empty-window pred 0 / margin 0 behaviour)."""
    if strategy == "tree":
        best_t, best_i, best_r = _butterfly((top1, gidx, top2), _merge_margin,
                                            axis, num_shards)
    else:
        t = jax.lax.all_gather(top1, axis)
        i = jax.lax.all_gather(gidx, axis)
        r = jax.lax.all_gather(top2, axis)
        best_t, best_i, best_r = t[0], i[0], r[0]
        for s in range(1, num_shards):
            best_t, best_i, best_r = _merge_margin(best_t, best_i, best_r,
                                                   t[s], i[s], r[s])
    top2g = jnp.maximum(best_r, best_t - cap)
    margin = jnp.where(jnp.isfinite(best_t), best_t - top2g, 0.0)
    return best_i.astype(jnp.int32), margin.astype(jnp.float32)


class MatchEngine:
    """Pluggable, mesh-aware Eq. 8-12 matching over a `TemplateBank`."""

    def __init__(self, config: EngineConfig = EngineConfig()):
        validate(config, backend_names())
        self.config = config

    def __repr__(self) -> str:
        return f"MatchEngine({self.config!r})"

    # -- backend resolution --------------------------------------------------

    def backend(self, n_elements: int | None = None) -> backends_lib.MatchBackend:
        """Resolve the backend ("auto" -> reference for tiny shapes).

        The tiny cutoff is per method (`repro.match.backends.tiny_cutoff`):
        the measured reference/kernel crossover sits ~16x higher for the
        similarity method than for feature_count."""
        name = self.config.backend
        if name == "auto":
            name = ("reference" if n_elements is not None
                    and n_elements < tiny_cutoff(self.config.method)
                    else "kernel")
        return backend_for(name, self.config)

    # -- plan-driven sharded execution ---------------------------------------

    def plan(self, batch: int, num_classes: int,
             be: backends_lib.MatchBackend) -> tuple[PartitionPlan, object]:
        """The `PartitionPlan` for a call with these static shapes."""
        return plan_for(batch=batch, num_classes=num_classes,
                        bank_shardable=be.supports_bank_sharding)

    def _shard(self, fn, batch_args: tuple, bank_args: tuple,
               plan: PartitionPlan, mesh, out_specs: tuple):
        """shard_map `fn(*batch_args, *bank_args)` under the plan: batch
        operands on the dp axes, class-row operands on the model axis."""
        in_specs = tuple(plan.batch_spec() for _ in batch_args) + tuple(
            bank_specs(plan) if isinstance(a, TemplateBank)
            else plan.class_spec() for a in bank_args)
        # check_vma=False: pallas_call has no replication rule; outputs are
        # either batch-local or made identical on every shard by the reduce.
        sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
        return sharded(*batch_args, *bank_args)

    @staticmethod
    def _row0(plan: PartitionPlan) -> Array:
        """This shard's first global class row (traced, inside shard_map)."""
        return jax.lax.axis_index(plan.model) * plan.rows_per_shard

    # -- raw score entry points (template arrays, not banks) -----------------

    def _raw_scores(self, queries: Array, bank_args: tuple, valid, fn):
        b = queries.shape[0]
        c, k = bank_args[0].shape[0], bank_args[0].shape[1]
        be = self.backend(b * c * k * queries.shape[-1])
        plan, mesh = self.plan(b, c, be)
        if not plan.sharded:
            return fn(be, queries, *bank_args, valid)
        if valid is None:
            valid = jnp.ones((c, k), bool)

        def run(q, *rest):
            return (fn(be, q, *rest),)

        return self._shard(run, (queries,), bank_args + (valid,), plan, mesh,
                           (plan.batch_class_spec(3),))[0]

    def feature_count_scores(self, queries: Array, templates: Array,
                             valid: Array | None = None) -> Array:
        """Eq. 8: queries (B, N) binary, templates (C, K, N) -> (B, C, K)."""
        return self._raw_scores(
            queries, (templates,), valid,
            lambda be, q, t, v: be.feature_count_scores(q, t, v))

    def similarity_scores(self, queries: Array, lower: Array, upper: Array,
                          valid: Array | None = None) -> Array:
        """Eq. 9-11: queries (B, N), windows (C, K, N) -> (B, C, K)."""
        alpha = self.config.alpha
        return self._raw_scores(
            queries, (lower, upper), valid,
            lambda be, q, lo, hi, v: be.similarity_scores(q, lo, hi, v,
                                                          alpha=alpha))

    # -- bank entry points ---------------------------------------------------

    def _elements(self, batch: int, bank: TemplateBank) -> int:
        c, k, n = bank.templates.shape
        return batch * c * k * n

    def scores(self, queries: Array, bank: TemplateBank) -> Array:
        """(B, C, K) scores for the configured method; invalid rows -inf."""
        be = self.backend(self._elements(queries.shape[0], bank))
        plan, mesh = self.plan(queries.shape[0], bank.templates.shape[0], be)
        if not plan.sharded:
            return be.scores(queries, bank)

        def fn(q, bk):
            # 1-tuple so the output pytree matches the out_specs tuple
            # (shard_map requires structural agreement, not a bare array)
            return (be.scores(q, bk),)

        return self._shard(fn, (queries,), (bank,), plan, mesh,
                           (plan.batch_class_spec(3),))[0]

    def _classify_via(self, shard_method: str, plain_method: str,
                      queries: Array, bank: TemplateBank
                      ) -> tuple[Array, Array]:
        be = self.backend(self._elements(queries.shape[0], bank))
        plan, mesh = self.plan(queries.shape[0], bank.templates.shape[0], be)
        if not plan.sharded:
            return getattr(be, plain_method)(queries, bank)
        if not plan.bank_sharded:
            return self._shard(getattr(be, plain_method), (queries,), (bank,),
                               plan, mesh,
                               (plan.batch_spec(1), plan.batch_spec(2)))

        def fn(q, bk):
            per_class, top1, gidx = getattr(be, shard_method)(
                q, bk, self._row0(plan))
            _, pred = _reduce_winner(top1, gidx, plan.model, plan.bank_shards,
                                     plan.reduce)
            return pred, per_class

        return self._shard(fn, (queries,), (bank,), plan, mesh,
                           (plan.batch_spec(1), plan.batch_class_spec(2)))

    def classify(self, queries: Array, bank: TemplateBank
                 ) -> tuple[Array, Array]:
        """Eq. 8/11 + Eq. 12 over *binary* queries -> (pred, per_class)."""
        return self._classify_via("classify_shard", "classify", queries, bank)

    def classify_features(self, features: Array, bank: TemplateBank
                          ) -> tuple[Array, Array]:
        """Raw features -> binarize -> match -> WTA -> (pred, per_class).

        The kernel backend executes this as a single fused pallas_call when
        the bank fits the fused layout; under a bank-sharded plan each
        device runs the fused kernel on its class-row shard and the winner
        comes from the cross-shard argmax reduce.
        """
        return self._classify_via("classify_features_shard",
                                  "classify_features", features, bank)

    def classify_features_margin(
        self, features: Array, bank: TemplateBank,
        class_lo: Array | None = None, class_hi: Array | None = None,
    ) -> tuple[Array, Array, Array]:
        """`classify_features` + per-request confidence margin (serving).

        Returns (pred (B,) int32 global class index, per_class (B, C),
        margin (B,) f32 clamped to the backend's score range). Empty class
        windows (slot padding) yield pred 0, margin 0. Class windows are
        global indices and may straddle bank shards — the margin reduce is
        exact either way (the serving registry still aligns tenant windows
        to shard boundaries so a tenant's rows share a device).
        """
        b = features.shape[0]
        c = bank.templates.shape[0]
        if class_lo is None:
            class_lo = jnp.zeros((b,), jnp.int32)
        if class_hi is None:
            class_hi = jnp.full((b,), c, jnp.int32)
        be = self.backend(self._elements(b, bank))
        plan, mesh = self.plan(b, c, be)
        if not plan.sharded:
            return be.classify_features_margin(features, bank, class_lo,
                                               class_hi)
        if not plan.bank_sharded:
            def fn(feats, lo, hi, bk):
                return be.classify_features_margin(feats, bk, lo, hi)

            return self._shard(fn, (features, class_lo, class_hi), (bank,),
                               plan, mesh, (plan.batch_spec(1),
                                            plan.batch_spec(2),
                                            plan.batch_spec(1)))
        cap = be.margin_cap(features.shape[-1])

        def fn(feats, lo, hi, bk):
            per_class, top1, gidx, top2 = be.classify_features_margin_shard(
                feats, bk, lo, hi, self._row0(plan))
            pred, margin = _reduce_margin(top1, gidx, top2, plan.model,
                                          plan.bank_shards, cap, plan.reduce)
            return pred, per_class, margin

        return self._shard(fn, (features, class_lo, class_hi), (bank,), plan,
                           mesh, (plan.batch_spec(1),
                                  plan.batch_class_spec(2),
                                  plan.batch_spec(1)))

    def classify_serve(self, features: Array, thr_table: Array,
                       tenant_slot: Array, bank: TemplateBank,
                       class_lo: Array | None = None,
                       class_hi: Array | None = None,
                       tau: Array | None = None
                       ) -> tuple[Array, Array, Array, Array]:
        """The multi-tenant serving tick: gather + margins + escalation.

        Each row binarises against its tenant's thresholds row
        (``thr_table[tenant_slot[i]]``) and classifies inside its class
        window; ``escalate[i] = margin[i] < tau[i]`` is the confidence
        cascade's per-request routing bit (tau -inf = never escalate — the
        padding/no-head value). Returns (pred, per_class, margin, escalate).

        On the kernel backend the whole thing is ONE pallas_call
        (`acam_match_serve` / `acam_similarity_serve`). Under a bank-sharded
        plan each device serves its class-row shard and `plan.reduce` picks
        the cross-shard merge (all-gather fold or the XOR-butterfly tree),
        then the tau compare runs on the reduced margin — all bit-identical
        to replicated execution.
        """
        b = features.shape[0]
        c = bank.templates.shape[0]
        if class_lo is None:
            class_lo = jnp.zeros((b,), jnp.int32)
        if class_hi is None:
            class_hi = jnp.full((b,), c, jnp.int32)
        if tau is None:
            tau = jnp.full((b,), -jnp.inf, jnp.float32)
        be = self.backend(self._elements(b, bank))
        plan, mesh = self.plan(b, c, be)
        if not plan.sharded:
            return be.classify_serve(features, thr_table, tenant_slot, bank,
                                     class_lo, class_hi, tau)
        batch_args = (features, tenant_slot, class_lo, class_hi, tau)
        if not plan.bank_sharded:
            def fn(feats, slot, lo, hi, t, thr, bk):
                return be.classify_serve(feats, thr, slot, bk, lo, hi, t)

            in_specs = tuple(plan.batch_spec() for _ in batch_args) + (
                P(), bank_specs(plan))
            sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                    out_specs=(plan.batch_spec(1),
                                               plan.batch_spec(2),
                                               plan.batch_spec(1),
                                               plan.batch_spec(1)),
                                    check_vma=False)
            return sharded(*batch_args, thr_table, bank)
        cap = be.margin_cap(features.shape[-1])

        def fn(feats, slot, lo, hi, t, thr, bk):
            per_class, top1, gidx, top2 = be.classify_serve_shard(
                feats, thr, slot, bk, lo, hi, self._row0(plan))
            pred, margin = _reduce_margin(top1, gidx, top2, plan.model,
                                          plan.bank_shards, cap, plan.reduce)
            return pred, per_class, margin, margin < t

        in_specs = tuple(plan.batch_spec() for _ in batch_args) + (
            P(), bank_specs(plan))
        sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=(plan.batch_spec(1),
                                           plan.batch_class_spec(2),
                                           plan.batch_spec(1),
                                           plan.batch_spec(1)),
                                check_vma=False)
        return sharded(*batch_args, thr_table, bank)

    def __call__(self, features: Array, bank: TemplateBank,
                 class_lo: Array | None = None,
                 class_hi: Array | None = None):
        """Config-directed forward: margins when `config.margin` is set."""
        if self.config.margin:
            return self.classify_features_margin(features, bank, class_lo,
                                                 class_hi)
        return self.classify_features(features, bank)

    # -- Monte-Carlo programming sweep (device backend) ----------------------

    def sweep_program_noise(self, features: Array, bank: TemplateBank,
                            keys: Array | int, *,
                            bank_shards: int | None = None
                            ) -> tuple[Array, Array]:
        """vmap the `sigma_program` programming draw over PRNG keys.

        The device backend's program-once-read-many flow draws ONE noisy
        array per engine config; this sweeps M independent programming
        draws in a single vmapped graph, turning point accuracies into
        confidence intervals on noisy-hardware behaviour.

        keys: an (M,)-leading array of PRNG keys, or an int M (keys are then
        split from ``PRNGKey(config.seed)``). Returns (pred (M, B) int32,
        per_class (M, B, C)). Requires ``backend="device"``; at
        ``sigma_program = 0`` every draw is the ideal array.

        Under ``device_noise="per_shard"`` each draw programs the S-array
        tiling (array s keyed ``fold_in(draw_key, s)`` — the same noise
        layout a bank-sharded plan realises per device). ``bank_shards``
        picks S; None infers it from the installed mesh
        (`repro.match.bank_shards_in_mesh`, 1 when the class count does not
        divide). Ignored under "global" noise (one array, one field).
        """
        be = self.backend(None)
        if not isinstance(be, backends_lib.DeviceBackend):
            raise ValueError(
                "sweep_program_noise requires the device backend; build the "
                'engine with engine_for(backend="device", device=ACAMConfig('
                "sigma_program=...))")
        shards = 1
        if be.per_shard_noise:
            c = bank.templates.shape[0]
            if bank_shards is None:
                bank_shards = plan_lib.bank_shards_in_mesh()
            shards = bank_shards if c % bank_shards == 0 else 1
        if isinstance(keys, int):
            keys = jax.random.split(jax.random.PRNGKey(self.config.seed),
                                    keys)
        return jax.vmap(
            lambda key: be.classify_features_keyed(features, bank, key,
                                                   bank_shards=shards)
        )(keys)


@functools.lru_cache(maxsize=None)
def _engine_for(config: EngineConfig) -> MatchEngine:
    return MatchEngine(config)


def engine_from_config(config: EngineConfig) -> MatchEngine:
    """Memoised engine for a fully-resolved `EngineConfig` (the spec path:
    `ServiceSpec.engine` and the scheduler tick hand the whole config over
    as one hashable static value instead of re-spelling its fields)."""
    return _engine_for(config)


def engine_for(method: str = "feature_count", alpha: float = 1.0,
               backend: str | None = None,
               block: tuple[int, int, int] | None = None,
               margin: bool = False, device=None, seed: int = 0,
               device_noise: str = "global") -> MatchEngine:
    """Memoised engine per config; `backend=None` -> the process default.

    The default is resolved HERE (eagerly, at the caller boundary), so a
    jitted caller that passes the resolved `engine.config` — or the backend
    name — as a static argument re-traces when the default changes.
    """
    cfg = EngineConfig(method=method, alpha=alpha,
                       backend=backend or default_backend(),
                       block=None if block is None else tuple(block),
                       margin=margin, device=device, seed=seed,
                       device_noise=device_noise)
    return _engine_for(cfg)
