"""Public wrappers for the acam_match kernels.

`match_scores` runs the raw-scores kernel (interpret=True on CPU, compiled
on TPU); `serve_classify` is the multi-tenant serving mega-kernel (per-slot
threshold gather, binarize, match, Eq. 12 WTA, windowed margin and
escalation mask in one dispatch); `classify_fused_margins` is its
single-tenant face — one shared thresholds row, no escalation.

Block sizes: when ``block`` is omitted the wrapper resolves a tuned
``(bm, bn, bk)`` via `repro.kernels.tuning.get_block` (persistent JSON cache
keyed by kernel|backend|shape|dtype, `DEFAULT_BLOCK` fallback). Resolution
is a pure dict lookup, so these wrappers stay safe to call at jit trace time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import layout, tuning
from repro.kernels.acam_match.acam_match import (
    DEFAULT_BLOCK, acam_match, acam_match_serve)


_on_cpu = tuning.interpret_mode
_resolve = functools.partial(tuning.resolve_block, "acam_match")


def match_scores(features: jax.Array, thresholds: jax.Array,
                 templates: jax.Array, *, block=None) -> jax.Array:
    block = _resolve(features, templates.shape[0], block)
    return acam_match(features, thresholds, templates, block=block,
                      interpret=_on_cpu())


def serve_classify(
        features: jax.Array, thr_table: jax.Array, tenant_slot: jax.Array,
        templates_ck: jax.Array, valid_ck: jax.Array,
        class_lo: jax.Array | None = None,
        class_hi: jax.Array | None = None, tau: jax.Array | None = None, *,
        max_rows: int, block=None
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The multi-tenant serving mega-kernel entry point (`acam_match_serve`).

    ONE pallas_call from raw per-slot features + the (T, N) per-tenant
    thresholds table to (pred, per_class, margin, escalate): the tenant
    threshold-row gather, binarisation, Eq. 8 match, per-class max, windowed
    Eq. 12 margin and the cascade's ``margin < tau`` escalation mask all run
    in VMEM. The class chunk degenerates to the padded class count for banks
    inside ``max_rows`` (fully resident) and tiles the class dimension past
    it — single dispatch at any bank size. ``tau`` defaults to -inf (never
    escalate); windows default to the whole bank.
    """
    c, k, n = templates_ck.shape
    b = features.shape[0]
    if class_lo is None:
        class_lo = jnp.zeros((b,), jnp.int32)
    if class_hi is None:
        class_hi = jnp.full((b,), c, jnp.int32)
    if tau is None:
        tau = jnp.full((b,), -jnp.inf, jnp.float32)
    # serve ticks are small (B = slots, N = front-end map width): never tile
    # past the data — bit-safe (see tuning.clamp_block) and a pure win
    block = tuning.clamp_block(_resolve(features, c * k, block), b, n)
    cp = layout.padded_classes(c)
    chunk = layout.class_chunk(cp, k, max_rows)
    t_kcp = layout.stack_kcp(templates_ck, c)
    v_kcp = layout.valid_kcp(valid_ck, c)
    return acam_match_serve(features, thr_table, tenant_slot, t_kcp, v_kcp,
                            class_lo, class_hi, tau, c, chunk=chunk,
                            block=block, interpret=_on_cpu())


def classify_fused_margins(
        features: jax.Array, thresholds: jax.Array, templates_ck: jax.Array,
        valid_ck: jax.Array, class_lo: jax.Array | None = None,
        class_hi: jax.Array | None = None, *, max_rows: int, block=None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-dispatch Eq. 8 + Eq. 12 + windowed margin (any bank size).

    The single-tenant face of the serve kernel: ONE shared thresholds row
    (T = 1, every query binarises against it) and tau pinned to -inf, with
    the escalation mask dropped. Returns (pred, per_class, margin) — the
    feature-count twin of `acam_similarity.ops.classify_fused_margins`.
    """
    b = features.shape[0]
    pred, per_class, margin, _ = serve_classify(
        features, thresholds[None, :], jnp.zeros((b,), jnp.int32),
        templates_ck, valid_ck, class_lo, class_hi, None,
        max_rows=max_rows, block=block)
    return pred, per_class, margin
