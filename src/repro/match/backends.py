"""Matching backends: the three first-class implementations of Eq. 8-12.

Every backend implements the same entry points over a
`TemplateBank` (or raw template arrays):

  feature_count_scores(queries, templates, valid)            -> (B, C, K)
  similarity_scores(queries, lower, upper, valid, alpha)     -> (B, C, K)
  classify(queries, bank)              binary queries        -> (pred, per_class)
  classify_features(features, bank)    raw features          -> (pred, per_class)
  classify_features_margin(features, bank, lo, hi)           -> (pred, per_class, margin)
  classify_serve(features, thr_table, slot, bank, lo, hi, tau)
                                       multi-tenant tick     -> (pred, per_class, margin, escalate)

Backends:

  reference  pure-jnp oracles — the parity baseline and the tiny-shape
             fast path (XLA fuses them well below the kernels' padding/
             launch overhead).
  kernel     one Pallas classify kernel per method (`acam_match_serve` /
             `acam_similarity_serve`): per-slot threshold gather ->
             binarize -> match -> valid-mask -> per-class max -> WTA ->
             windowed margin -> escalation mask in ONE pallas_call at ANY
             bank size — banks inside `MAX_FUSED_ROWS` keep every template
             row VMEM-resident, bigger banks walk the class dimension in
             chunks (same single dispatch, running per-class max in a
             revisited block). `classify`, `classify_features` and
             `classify_features_margin` run its single-tenant face (T = 1,
             no escalation). Blocks resolve through the
             `repro.kernels.tuning` autotuner unless the engine config pins
             them.
  device     the RRAM-CMOS physics models from `repro.core.acam` (§III):
             the bank is programmed into a (C*K)-row TXL array (point
             templates become lower == upper windows), optionally with
             log-normal `sigma_program` write noise, and scores are the
             analogue sense-amplifier outputs. 6T4R senses the matchline
             charge fraction, 3T1R the dual-rail survival fraction — both
             equal the in-window fraction at sigma_program = 0, so classify
             decisions match the reference backend exactly at zero noise
             while scores/margins are in matchline units (cap 1.0, not N).
             The Eq. 9 distance term is digital post-processing the
             matchline does not integrate, so `alpha` is ignored here.

Register additional backends with `register_backend(name, factory)`; the
factory takes the `EngineConfig` so backends can read `block`, `device`,
`seed`, ...
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import acam as acam_lib
from repro.core import quant
from repro.core.templates import TemplateBank
from repro.match.config import EngineConfig

Array = jax.Array

NEG = -jnp.inf

#: below this many (B * C * K * N) cell-match operations the jnp reference
#: beats the kernel's padding/launch overhead — "auto" stays on XLA. This
#: is the feature-count crossover; the similarity kernel does ~16x less
#: useful work per microsecond (BENCH_kernels.json: 584 vs 40.5
#: cell-matches/us at B=256), so its crossover sits ~16x later.
TINY_ELEMENTS = 32768

#: the similarity method's crossover: TINY_ELEMENTS * 16 (measured ratio of
#: kernel cell-matches/us between the two methods).
TINY_ELEMENTS_SIMILARITY = 524288


def tiny_cutoff(method: str) -> int:
    """Per-method "auto" dispatch cutoff in B * C * K * N cell matches."""
    return TINY_ELEMENTS_SIMILARITY if method == "similarity" \
        else TINY_ELEMENTS


#: the serve kernels keep all K * Cp template rows VMEM-resident up to this
#: row count and walk the bank in class-column chunks past it (still a
#: single pallas_call — `layout.class_chunk`).
MAX_FUSED_ROWS = 2048


# ---------------------------------------------------------------------------
# Shared epilogues (pure jnp)
# ---------------------------------------------------------------------------

def classify_scores(scores: Array) -> tuple[Array, Array]:
    """Eq. 12 with multi-template max-pooling.

    scores: (B, C, K) -> (pred (B,), per_class (B, C)).
    """
    per_class = jnp.max(scores, axis=-1)
    return jnp.argmax(per_class, axis=-1), per_class


def winner_take_all(per_class: Array) -> Array:
    """One-hot WTA output (the analogue WTA network's digital semantics)."""
    return jax.nn.one_hot(jnp.argmax(per_class, axis=-1), per_class.shape[-1])


def shard_window_top2(per_class: Array, class_lo: Array | None,
                      class_hi: Array | None, row0: Array
                      ) -> tuple[Array, Array, Array]:
    """Shard-local windowed (top1, winner index, top2) — the margin partial.

    ``per_class`` is this shard's (B, C_local) slice of the per-class
    scores; ``row0`` the shard's first *global* class row; windows are
    global class indices (they may straddle shards — the iota offset
    intersects them with this shard's rows). Returns the three (B,) partials
    the engine's cross-shard margin reduce combines: the top1 value, its
    global class index (lowest-first among local ties, like `jnp.argmax`),
    and the runner-up *excluding only the winner's position* (so a tied
    class elsewhere yields top2 == top1, margin 0 — exactly
    `repro.kernels.layout.windowed_margin` semantics). No cap clamp here:
    the clamp is a global property, applied after the reduce.
    """
    b, c = per_class.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    giota = iota + row0
    if class_lo is not None:
        win = (giota >= class_lo[:, None]) & (giota < class_hi[:, None])
        s = jnp.where(win, per_class, NEG)
    else:
        s = per_class
    local = jnp.argmax(s, axis=-1)
    top1 = jnp.take_along_axis(s, local[:, None], axis=-1)[:, 0]
    top2 = jnp.max(jnp.where(iota == local[:, None], NEG, s), axis=-1)
    return top1, (local + row0).astype(jnp.int32), top2


def window_margin(per_class: Array, class_lo: Array | None = None,
                  class_hi: Array | None = None, *,
                  cap: float) -> tuple[Array, Array]:
    """Eq. 12 decision + winner-vs-runner-up margin inside class windows.

    jnp oracle for the serve kernels' margin, and the epilogue of the
    reference and device paths. ``per_class`` is (B, C) with -inf for
    invalid classes; windows default to the full class range.
    Returns (pred (B,) int32 global class index, margin (B,) f32 clamped to
    cap).
    """
    b, c = per_class.shape
    if class_lo is None:
        class_lo = jnp.zeros((b,), jnp.int32)
    if class_hi is None:
        class_hi = jnp.full((b,), c, jnp.int32)
    from repro.kernels.layout import windowed_margin
    return windowed_margin(per_class, class_lo.astype(jnp.int32)[:, None],
                           class_hi.astype(jnp.int32)[:, None], cap)


# ---------------------------------------------------------------------------
# Pure-jnp references (the parity oracles; also the tiny-shape fast path)
# ---------------------------------------------------------------------------

def feature_count_scores_ref(queries: Array, templates: Array,
                             valid: Array | None = None) -> Array:
    """Eq. 8 reference: materialises the (B, C, K, N) comparison in HBM."""
    eq = queries[:, None, None, :] == templates[None, :, :, :]
    scores = jnp.sum(eq, axis=-1).astype(jnp.float32)
    if valid is not None:
        scores = jnp.where(valid[None, :, :], scores, NEG)
    return scores


def similarity_scores_ref(
    queries: Array,
    lower: Array,
    upper: Array,
    valid: Array | None = None,
    *,
    alpha: float = 1.0,
) -> Array:
    """Eq. 9-11 reference: materialises the (B, C, K, N) intermediate."""
    q = queries[:, None, None, :]
    lo = lower[None, :, :, :]
    hi = upper[None, :, :, :]
    above = jnp.maximum(q - hi, 0.0)
    below = jnp.maximum(lo - q, 0.0)
    d = jnp.sum(above**2 + below**2, axis=-1)  # Eq. 9
    hits = jnp.sum(((q >= lo) & (q <= hi)).astype(jnp.float32), axis=-1)
    from repro.kernels.acam_similarity.acam_similarity import eq11
    s = eq11(hits, d, q.shape[-1], alpha)  # Eq. 10-11
    if valid is not None:
        s = jnp.where(valid[None, :, :], s, NEG)
    return s


# ---------------------------------------------------------------------------
# Backend protocol
# ---------------------------------------------------------------------------

class MatchBackend:
    """Base class: default implementations compose the two score entry
    points with the shared jnp epilogues; subclasses override the hot paths
    they can fuse."""

    name = "base"

    #: whether the engine may cut this backend's bank into class-row shards
    #: (`repro.match.plan`). True for the digital backends — their scores
    #: are row-independent, so a shard computes bit-identical per-class
    #: values. The device backend overrides this (programming noise is drawn
    #: per physical array, not per shard).
    supports_bank_sharding = True

    def __init__(self, config: EngineConfig):
        self.config = config

    # -- scores --------------------------------------------------------------

    def feature_count_scores(self, queries: Array, templates: Array,
                             valid: Array | None = None) -> Array:
        raise NotImplementedError

    def similarity_scores(self, queries: Array, lower: Array, upper: Array,
                          valid: Array | None = None, *,
                          alpha: float = 1.0) -> Array:
        raise NotImplementedError

    def scores(self, queries: Array, bank: TemplateBank) -> Array:
        if self.config.method == "feature_count":
            return self.feature_count_scores(queries, bank.templates,
                                             bank.valid)
        return self.similarity_scores(queries, bank.lower, bank.upper,
                                      bank.valid, alpha=self.config.alpha)

    # -- classify ------------------------------------------------------------

    def classify(self, queries: Array, bank: TemplateBank
                 ) -> tuple[Array, Array]:
        """Eq. 8/11 + Eq. 12 over *binary* queries."""
        return classify_scores(self.scores(queries, bank))

    def classify_features(self, features: Array, bank: TemplateBank
                          ) -> tuple[Array, Array]:
        """Raw front-end features -> binarize -> match -> WTA (Fig. 2)."""
        return self.classify(quant.binarize(features, bank.thresholds), bank)

    def margin_cap(self, num_features: int) -> float:
        """Score range the margin is clamped to (empty-runner-up guard)."""
        return (float(num_features) if self.config.method == "feature_count"
                else 1.0)

    def classify_features_margin(
        self, features: Array, bank: TemplateBank,
        class_lo: Array | None = None, class_hi: Array | None = None,
    ) -> tuple[Array, Array, Array]:
        _, per_class = self.classify_features(features, bank)
        pred, margin = window_margin(per_class, class_lo, class_hi,
                                     cap=self.margin_cap(features.shape[-1]))
        return pred, per_class, margin

    # -- multi-tenant serve path (the scheduler tick) ------------------------
    #
    # One micro-batch of per-slot raw features, each row binarising against
    # ITS tenant's threshold row of a stacked (T, N) table, matched over the
    # shared super-bank (whose own thresholds are zeros — the registry packs
    # tenants that way), inside per-row class windows, with the cascade's
    # escalation compare folded in. The default composes existing pieces
    # (gather + the shift identity binarize(f, thr_t) == binarize(f - thr_t,
    # 0) + classify_features_margin + margin < tau); the kernel backend
    # overrides it with the serve kernel.

    def classify_serve(
        self, features: Array, thr_table: Array, tenant_slot: Array,
        bank: TemplateBank, class_lo: Array, class_hi: Array, tau: Array,
    ) -> tuple[Array, Array, Array, Array]:
        """-> (pred, per_class, margin, escalate (B,) bool)."""
        thr_rows = jnp.take(thr_table, tenant_slot, axis=0)
        pred, per_class, margin = self.classify_features_margin(
            features - thr_rows, bank, class_lo, class_hi)
        return pred, per_class, margin, margin < tau

    def classify_serve_shard(
        self, features: Array, thr_table: Array, tenant_slot: Array,
        bank: TemplateBank, class_lo: Array, class_hi: Array, row0: Array,
    ) -> tuple[Array, Array, Array, Array]:
        """Bank-sharded serve partials: gather + shift on this shard, then
        the margin partials (per_class, top1, gidx, top2) — the engine's
        cross-shard reduce recovers the global decision and applies tau."""
        thr_rows = jnp.take(thr_table, tenant_slot, axis=0)
        return self.classify_features_margin_shard(
            features - thr_rows, bank, class_lo, class_hi, row0)

    # -- shard-local classify (bank-sharded execution, repro.match.plan) -----
    #
    # Under a bank-sharded PartitionPlan each device holds only class rows
    # [row0, row0 + C_local) of the bank. These entry points run the
    # backend's (fused) classify on that shard and return row-offset-aware
    # partials — per_class plus the (top1, global winner index[, top2])
    # summary the engine's cross-shard (max, argmax) reduce combines into
    # the exact global Eq. 12 decision and margin.

    def classify_shard(self, queries: Array, bank: TemplateBank, row0: Array
                       ) -> tuple[Array, Array, Array]:
        """Binary queries -> (per_class (B, C_local), top1 (B,), gidx (B,))."""
        pred, per_class = self.classify(queries, bank)
        return per_class, jnp.max(per_class, axis=-1), \
            (pred + row0).astype(jnp.int32)

    def classify_features_shard(self, features: Array, bank: TemplateBank,
                                row0: Array) -> tuple[Array, Array, Array]:
        """Raw features -> (per_class (B, C_local), top1 (B,), gidx (B,))."""
        pred, per_class = self.classify_features(features, bank)
        return per_class, jnp.max(per_class, axis=-1), \
            (pred + row0).astype(jnp.int32)

    def classify_features_margin_shard(
        self, features: Array, bank: TemplateBank, class_lo: Array,
        class_hi: Array, row0: Array,
    ) -> tuple[Array, Array, Array, Array]:
        """Margin partials: (per_class, top1, gidx, top2), windows global."""
        _, per_class = self.classify_features(features, bank)
        top1, gidx, top2 = shard_window_top2(per_class, class_lo, class_hi,
                                             row0)
        return per_class, top1, gidx, top2


# ---------------------------------------------------------------------------
# reference backend
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("method", "alpha"))
def _classify_ref(queries: Array, bank: TemplateBank, *, method: str,
                  alpha: float) -> tuple[Array, Array]:
    if method == "feature_count":
        scores = feature_count_scores_ref(queries, bank.templates, bank.valid)
    else:
        scores = similarity_scores_ref(queries, bank.lower, bank.upper,
                                       bank.valid, alpha=alpha)
    return classify_scores(scores)


class ReferenceBackend(MatchBackend):
    name = "reference"

    def feature_count_scores(self, queries, templates, valid=None):
        return feature_count_scores_ref(queries, templates, valid)

    def similarity_scores(self, queries, lower, upper, valid=None, *,
                          alpha=1.0):
        return similarity_scores_ref(queries, lower, upper, valid,
                                     alpha=alpha)

    def classify(self, queries, bank):
        return _classify_ref(queries, bank, method=self.config.method,
                             alpha=self.config.alpha)


# ---------------------------------------------------------------------------
# kernel backend (Pallas)
# ---------------------------------------------------------------------------

def _binary_thresholds(n: int) -> Array:
    # binary {0,1} queries re-binarise exactly through a 0.5 threshold,
    # letting the kernels' fused binarisation stage pass them through.
    # Always float32: a bool-dtype 0.5 would collapse to True and binarise
    # every query bit to 0.
    return jnp.full((n,), 0.5, jnp.float32)


class KernelBackend(MatchBackend):
    name = "kernel"

    def feature_count_scores(self, queries, templates, valid=None):
        from repro.kernels.acam_match import ops as match_ops

        b, n = queries.shape
        c, k, _ = templates.shape
        flat = match_ops.match_scores(
            queries.astype(jnp.float32), _binary_thresholds(n),
            templates.reshape(c * k, n).astype(jnp.float32),
            block=self.config.block)
        scores = flat.reshape(b, c, k)
        if valid is not None:
            scores = jnp.where(valid[None, :, :], scores, NEG)
        return scores

    def similarity_scores(self, queries, lower, upper, valid=None, *,
                          alpha=1.0):
        from repro.kernels.acam_similarity import ops as sim_ops

        b, n = queries.shape
        c, k, _ = lower.shape
        flat = sim_ops.similarity_scores(queries, lower.reshape(c * k, n),
                                         upper.reshape(c * k, n),
                                         alpha=alpha, block=self.config.block)
        s = flat.reshape(b, c, k)
        if valid is not None:
            s = jnp.where(valid[None, :, :], s, NEG)
        return s

    def classify(self, queries, bank):
        return self.classify_features(
            queries.astype(jnp.float32), bank._replace(
                thresholds=_binary_thresholds(queries.shape[-1])))

    def classify_features(self, features, bank):
        pred, per_class, _ = self.classify_features_margin(features, bank)
        return pred, per_class

    def classify_features_margin(self, features, bank, class_lo=None,
                                 class_hi=None):
        """The serve kernel's single-tenant face (one shared thresholds row,
        no escalation): ONE pallas_call at any bank size, fully resident
        inside `MAX_FUSED_ROWS`, class-chunked past it."""
        from repro.kernels.acam_match import ops as match_ops
        from repro.kernels.acam_similarity import ops as sim_ops

        if self.config.method == "feature_count":
            return match_ops.classify_fused_margins(
                features.astype(jnp.float32), bank.thresholds,
                bank.templates, bank.valid, class_lo, class_hi,
                max_rows=MAX_FUSED_ROWS, block=self.config.block)
        return sim_ops.classify_fused_margins(
            features, bank.thresholds, bank.lower, bank.upper, bank.valid,
            class_lo, class_hi, alpha=self.config.alpha,
            max_rows=MAX_FUSED_ROWS, block=self.config.block)

    def classify_serve(self, features, thr_table, tenant_slot, bank,
                       class_lo, class_hi, tau):
        """The serving mega-kernel: the whole multi-tenant tick — gather,
        binarize, match, per-class max, WTA, windowed margin, escalation
        mask — in ONE pallas_call for BOTH methods."""
        from repro.kernels.acam_match import ops as match_ops
        from repro.kernels.acam_similarity import ops as sim_ops

        if self.config.method == "feature_count":
            return match_ops.serve_classify(
                features.astype(jnp.float32), thr_table, tenant_slot,
                bank.templates, bank.valid, class_lo, class_hi, tau,
                max_rows=MAX_FUSED_ROWS, block=self.config.block)
        return sim_ops.serve_classify(
            features, thr_table, tenant_slot, bank.lower, bank.upper,
            bank.valid, class_lo, class_hi, tau, alpha=self.config.alpha,
            max_rows=MAX_FUSED_ROWS, block=self.config.block)


# ---------------------------------------------------------------------------
# device backend (RRAM-CMOS physics, repro.core.acam)
# ---------------------------------------------------------------------------

class DeviceBackend(MatchBackend):
    """Matching through the §III TXL-ACAM behavioural models.

    The bank is flattened class-major into a (C*K, N) array and *programmed*
    (`acam.program`): point templates become degenerate lower == upper
    windows, window templates keep their bounds. `sigma_program > 0` applies
    the log-normal RRAM write noise, keyed by the engine config's seed, so
    noisy-hardware accuracy/energy sweeps run through the exact same API as
    the ideal backends. Scores are `acam.sense` outputs — the matchline
    charge fraction (6T4R) or dual-rail survival fraction (3T1R) — in [0, 1]
    matchline units (margins cap at 1.0, not N).
    """

    name = "device"

    def __init__(self, config: EngineConfig):
        super().__init__(config)
        self.acam_config = config.device or acam_lib.ACAMConfig()

    @property
    def per_shard_noise(self) -> bool:
        """Per-shard programming keys (`EngineConfig.device_noise`): real
        tiled deployments program one physical array per bank shard, so
        array s draws its write noise from ``fold_in(PRNGKey(seed), s)``."""
        return self.config.device_noise == "per_shard"

    @property
    def supports_bank_sharding(self) -> bool:
        # "global" noise: sigma_program > 0 draws one noise field per
        # *programmed array*; programming per-shard sub-arrays with the same
        # key would realise a different noise layout than the replicated
        # array, breaking the engine's bit-identical-to-replicated contract.
        # The ideal array (sigma = 0) is row-independent and shards exactly,
        # and "per_shard" noise makes the tiled layout the *defined*
        # semantics (one programming key per shard), lifting the refusal.
        return self.acam_config.sigma_program <= 0.0 or self.per_shard_noise

    def _program_rows(self, lower: Array, upper: Array, valid_flat: Array,
                      key: Array | None = None) -> acam_lib.ProgrammedACAM:
        if key is None and self.acam_config.sigma_program > 0.0:
            key = jax.random.PRNGKey(self.config.seed)
        return acam_lib.program(lower, upper, valid_flat, self.acam_config,
                                key)

    def _bank_rows(self, bank: TemplateBank) -> tuple[Array, Array, Array]:
        c, k, n = bank.templates.shape
        if self.config.method == "feature_count":
            lo = hi = bank.templates.reshape(c * k, n)
        else:
            lo = bank.lower.reshape(c * k, n)
            hi = bank.upper.reshape(c * k, n)
        return lo, hi, bank.valid.reshape(c * k)

    def program_bank(self, bank: TemplateBank, key: Array | None = None,
                     *, shard_index: Array | int = 0,
                     bank_shards: int = 1) -> acam_lib.ProgrammedACAM:
        """The acam.py bridge: bank -> programmed (C*K, N) TXL array.

        Public so calibration flows (`acam.calibrate_windows`,
        `acam.soft_sense` gradients) can reach the exact array the engine
        matches against. ``key`` overrides the config-seed programming draw
        (the Monte-Carlo sweep's per-draw keys); None keeps the
        program-once-read-many default.

        Under ``device_noise="per_shard"`` the programming key is
        ``fold_in(base, shard_index)``: inside a bank-sharded shard_map the
        engine passes this shard's index, and ``bank_shards > 1`` *emulates*
        the S-array tiling on a replicated bank — class rows are programmed
        in S per-shard groups keyed ``fold_in(base, s)``, bit-identical to
        what the sharded execution realises per device.
        """
        lo, hi, valid = self._bank_rows(bank)
        sigma = self.acam_config.sigma_program
        if sigma <= 0.0 or not self.per_shard_noise:
            return self._program_rows(lo, hi, valid, key)
        base = key if key is not None \
            else jax.random.PRNGKey(self.config.seed)
        if bank_shards <= 1:
            return self._program_rows(lo, hi, valid,
                                      jax.random.fold_in(base, shard_index))
        c = bank.templates.shape[0]
        if c % bank_shards:
            raise ValueError(
                f"per-shard programming emulation needs class rows ({c}) "
                f"divisible by bank_shards ({bank_shards})")
        rows = lo.shape[0] // bank_shards  # = (C/S) * K rows per array
        progs = [self._program_rows(lo[s * rows:(s + 1) * rows],
                                    hi[s * rows:(s + 1) * rows],
                                    valid[s * rows:(s + 1) * rows],
                                    jax.random.fold_in(base, s))
                 for s in range(bank_shards)]
        return acam_lib.ProgrammedACAM(
            lower=jnp.concatenate([p.lower for p in progs]),
            upper=jnp.concatenate([p.upper for p in progs]),
            valid=jnp.concatenate([p.valid for p in progs]),
            config=progs[0].config)

    def _sense_rows(self, prog: acam_lib.ProgrammedACAM, queries: Array,
                    c: int, k: int) -> Array:
        s = acam_lib.sense(prog, queries)  # (B, C*K), invalid rows -> -inf
        return s.reshape(queries.shape[0], c, k)

    def feature_count_scores(self, queries, templates, valid=None):
        c, k, n = templates.shape
        flat = templates.reshape(c * k, n)
        v = (valid if valid is not None
             else jnp.ones((c, k), bool)).reshape(c * k)
        return self._sense_rows(self._program_rows(flat, flat, v), queries,
                                c, k)

    def similarity_scores(self, queries, lower, upper, valid=None, *,
                          alpha=1.0):
        # alpha (the Eq. 9/11 distance weight) is digital post-processing
        # the analogue matchline does not integrate: the device senses the
        # Eq. 10 in-window fraction H only.
        del alpha
        c, k, n = lower.shape
        v = (valid if valid is not None
             else jnp.ones((c, k), bool)).reshape(c * k)
        prog = self._program_rows(lower.reshape(c * k, n),
                                  upper.reshape(c * k, n), v)
        return self._sense_rows(prog, queries, c, k)

    def scores(self, queries, bank):
        c, k, _ = bank.templates.shape
        return self._sense_rows(self.program_bank(bank), queries, c, k)

    def classify_features_keyed(self, features: Array, bank: TemplateBank,
                                key: Array, *, bank_shards: int = 1
                                ) -> tuple[Array, Array]:
        """One Monte-Carlo draw: program the bank with an explicit PRNG key
        (instead of the config-seed key) and classify.

        vmap-safe over ``key`` — `MatchEngine.sweep_program_noise` maps this
        over a batch of keys to turn the single programming sample of the
        program-once flow into per-draw confidence intervals. Under
        ``device_noise="per_shard"``, ``bank_shards=S`` programs the S-array
        tiling (array s keyed ``fold_in(key, s)``).
        """
        c, k, _ = bank.templates.shape
        prog = self.program_bank(bank, key, bank_shards=bank_shards)
        q = quant.binarize(features, bank.thresholds)
        return classify_scores(self._sense_rows(prog, q, c, k))

    # -- shard-local entry points (bank-sharded plans) -----------------------
    #
    # Each device programs its OWN physical array: under "per_shard" noise
    # the programming key folds in the shard index (row0 / C_local), so
    # shard s realises the same noise field whether it runs sharded on
    # device s or is emulated by `program_bank(..., bank_shards=S)`.

    def _shard_scores(self, queries: Array, bank: TemplateBank, row0: Array
                      ) -> Array:
        c, k, _ = bank.templates.shape
        prog = self.program_bank(bank, shard_index=row0 // c)
        return self._sense_rows(prog, queries, c, k)

    def classify_shard(self, queries, bank, row0):
        pred, per_class = classify_scores(
            self._shard_scores(queries, bank, row0))
        return per_class, jnp.max(per_class, axis=-1), \
            (pred + row0).astype(jnp.int32)

    def classify_features_shard(self, features, bank, row0):
        q = quant.binarize(features, bank.thresholds)
        return self.classify_shard(q, bank, row0)

    def classify_features_margin_shard(self, features, bank, class_lo,
                                       class_hi, row0):
        q = quant.binarize(features, bank.thresholds)
        _, per_class = classify_scores(self._shard_scores(q, bank, row0))
        top1, gidx, top2 = shard_window_top2(per_class, class_lo, class_hi,
                                             row0)
        return per_class, top1, gidx, top2

    def margin_cap(self, num_features: int) -> float:
        return 1.0  # sense outputs live in [0, 1] matchline units


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[EngineConfig], MatchBackend]] = {
    "reference": ReferenceBackend,
    "kernel": KernelBackend,
    "device": DeviceBackend,
}


def register_backend(name: str,
                     factory: Callable[[EngineConfig], MatchBackend]) -> None:
    """Add (or replace) a backend. `factory(config)` -> MatchBackend."""
    if name == "auto":
        raise ValueError('"auto" is the engine dispatch policy, '
                         "not a backend name")
    _REGISTRY[name] = factory
    backend_for.cache_clear()  # a replaced factory must not serve stale hits


def backend_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


@functools.lru_cache(maxsize=None)
def backend_for(name: str, config: EngineConfig) -> MatchBackend:
    """Memoised backend instance per (name, config) — backends are
    stateless value objects, so sharing them keeps jit caches shared too."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown matching backend {name!r}; use "
                         f"{('auto',) + backend_names()}") from None
    return factory(config)
