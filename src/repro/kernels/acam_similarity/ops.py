"""Public wrappers for the acam_similarity kernel.

`similarity_scores` runs the raw-scores kernel; `serve_classify` is the
multi-tenant serving mega-kernel (per-slot threshold gather + margins +
escalation mask in VMEM, class-chunked past ``max_rows``) — the similarity
twin of `repro.kernels.acam_match.ops.serve_classify`;
`classify_fused_margins` is its single-tenant face.

Blocks resolve through `repro.kernels.tuning.get_block` (persistent JSON
cache, `DEFAULT_BLOCK` fallback) when ``block`` is omitted — a pure lookup,
safe at jit trace time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import layout, tuning
from repro.kernels.acam_similarity.acam_similarity import (
    DEFAULT_BLOCK, acam_similarity, acam_similarity_serve)


_on_cpu = tuning.interpret_mode
_resolve = functools.partial(tuning.resolve_block, "acam_similarity")


def similarity_scores(queries: jax.Array, lower: jax.Array, upper: jax.Array,
                      *, alpha: float = 1.0, block=None) -> jax.Array:
    block = _resolve(queries, lower.shape[0], block)
    return acam_similarity(queries, lower, upper, alpha=alpha, block=block,
                           interpret=_on_cpu())


def serve_classify(
        features: jax.Array, thr_table: jax.Array, tenant_slot: jax.Array,
        lower_ck: jax.Array, upper_ck: jax.Array, valid_ck: jax.Array,
        class_lo: jax.Array | None = None,
        class_hi: jax.Array | None = None, tau: jax.Array | None = None, *,
        alpha: float = 1.0, max_rows: int, block=None
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Multi-tenant serving mega-kernel over a (C, K, N) window bank.

    Same contract as `repro.kernels.acam_match.ops.serve_classify` with
    Eq. 9-11 scoring: ONE pallas_call from raw features + the (T, N)
    thresholds table to (pred, per_class, margin, escalate), class-chunked
    past ``max_rows`` template rows. ``tau`` defaults to -inf.
    """
    c, k, n = lower_ck.shape
    b = features.shape[0]
    if class_lo is None:
        class_lo = jnp.zeros((b,), jnp.int32)
    if class_hi is None:
        class_hi = jnp.full((b,), c, jnp.int32)
    if tau is None:
        tau = jnp.full((b,), -jnp.inf, jnp.float32)
    # never tile past the data (see tuning.clamp_block): bit-safe, and the
    # serving tick's B = slots / small-N regime is exactly where it pays
    block = tuning.clamp_block(_resolve(features, c * k, block), b, n)
    cp = layout.padded_classes(c)
    chunk = layout.class_chunk(cp, k, max_rows)
    lo_kcp = layout.stack_kcp(lower_ck, c)
    hi_kcp = layout.stack_kcp(upper_ck, c)
    v_kcp = layout.valid_kcp(valid_ck, c)
    return acam_similarity_serve(features, thr_table, tenant_slot, lo_kcp,
                                 hi_kcp, v_kcp, class_lo, class_hi, tau, c,
                                 alpha=alpha, chunk=chunk, block=block,
                                 interpret=_on_cpu())


def classify_fused_margins(
        features: jax.Array, thresholds: jax.Array, lower_ck: jax.Array,
        upper_ck: jax.Array, valid_ck: jax.Array,
        class_lo: jax.Array | None = None,
        class_hi: jax.Array | None = None, *, alpha: float = 1.0,
        max_rows: int, block=None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-dispatch Eq. 9-12 + windowed margin (any bank size).

    The single-tenant face of the serve kernel: ONE shared thresholds row
    (T = 1, every query binarises against it) and tau pinned to -inf, with
    the escalation mask dropped. Returns (pred, per_class, margin) — the
    similarity twin of `acam_match.ops.classify_fused_margins`.
    """
    b = features.shape[0]
    pred, per_class, margin, _ = serve_classify(
        features, thresholds[None, :], jnp.zeros((b,), jnp.int32), lower_ck,
        upper_ck, valid_ck, class_lo, class_hi, None, alpha=alpha,
        max_rows=max_rows, block=block)
    return pred, per_class, margin
