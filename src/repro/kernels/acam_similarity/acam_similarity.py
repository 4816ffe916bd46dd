"""Pallas TPU kernel: ACAM similarity matching (paper Eq. 9-11).

Per (query, template) pair with matching window [T^L, T^U] per cell:

    D = sum_i relu(Q_i - U_i)^2 + relu(L_i - Q_i)^2       (Eq. 9)
    H = (1/N) sum_i 1(L_i <= Q_i <= U_i)                  (Eq. 10)
    S = H / (1 + alpha * D)                               (Eq. 11)

This is the behavioural model of the analogue TXL array: D is the
out-of-window penalty, H the matchline hit fraction. The kernel is a
bandwidth-bound VPU fusion: grid (B/bm, M/bn, N/bk), broadcasting query and
window blocks to a (bm, bn, bk) VMEM tile, accumulating D and H into two
(bm, bn) f32 VMEM accumulators across the k loop, applying the Eq. 11
epilogue on the last k step — the (B, M, N) intermediate never exists in
HBM (the jnp oracle materialises it, which is exactly why this kernel
exists).

Block sizes come from `repro.kernels.tuning` (persistent JSON cache at
``$REPRO_TUNING_CACHE`` / ``~/.cache/repro/pallas_blocks.json``, keyed
``kernel|backend|shape|dtype``) with `DEFAULT_BLOCK` as the untuned
fallback. Two entry points:

  `acam_similarity`        -> (B, M) Eq. 11 scores, behind the engine's
                              explicit `*_scores` entry points.
  `acam_similarity_serve`  -> the classify kernel: the (K, Cp, N)
                              class-chunked scheme of `acam_match_serve`
                              for the similarity method — per-slot tenant
                              threshold gather, binarize, Eq. 9-11 window
                              match with D/H chunk accumulators, running
                              per-class max, windowed Eq. 12 margin and the
                              escalation mask, in one dispatch at any bank
                              size (the chunk degenerates to Cp for
                              resident banks). A single-tenant classify is
                              its T = 1 face (`ops.classify_fused_margins`).

The jnp references in `repro.match.backends` remain as oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (8, 128, 128)  # bm (queries), bn (templates), bk (features)
PRED_LANES = 128  # WTA index output padded to one lane tile


def eq11(hits: jax.Array, d: jax.Array, n: int, alpha: float) -> jax.Array:
    """Eq. 10-11 from the hit COUNT: S = H_count / (N (1 + alpha D)).

    One division of a runtime denominator, the same op in these kernels
    and in the jnp oracles, so the two round alike on a TPU. The textbook
    form (H_count / N) / (1 + alpha D) divides by the constant N, which XLA
    turns into a multiply by 1/N and Mosaic does not: on a TPU v5e that
    put the kernel an ulp away from the reference (1.0000001 for a perfect
    match), which breaks ties differently."""
    return hits / (float(n) * (1.0 + alpha * d))


def _kernel(q_ref, lo_ref, hi_ref, d_ref, h_ref, s_ref, *, nk: int,
            alpha: float, n_true: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        d_ref[...] = jnp.zeros_like(d_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    q = q_ref[...][:, None, :]  # (bm, 1, bk)
    lo = lo_ref[...][None, :, :]  # (1, bn, bk)
    hi = hi_ref[...][None, :, :]

    above = jnp.maximum(q - hi, 0.0)
    below = jnp.maximum(lo - q, 0.0)
    d_ref[...] += jnp.sum(above * above + below * below, axis=-1)
    hit = jnp.logical_and(q >= lo, q <= hi)
    h_ref[...] += jnp.sum(hit.astype(jnp.float32), axis=-1)

    @pl.when(k == nk - 1)
    def _epilogue():
        # padded feature columns have lo=0=hi and q=0 => they count as hits;
        # subtract the pad count from H before normalising by the true N.
        pad_hits = float(nk * q_ref.shape[-1] - n_true)
        s_ref[...] = eq11(h_ref[...] - pad_hits, d_ref[...], n_true, alpha)


@functools.partial(jax.jit, static_argnames=("alpha", "block", "interpret"))
def acam_similarity(queries: jax.Array, lower: jax.Array, upper: jax.Array,
                    *, alpha: float = 1.0, block=DEFAULT_BLOCK,
                    interpret: bool = False) -> jax.Array:
    """Similarity scores (B, M) for window templates.

    queries: (B, N); lower/upper: (M, N) with lower <= upper.
    """
    b, n = queries.shape
    m = lower.shape[0]
    bm, bn, bk = block
    bp, mp, np_ = (-(-b // bm) * bm, -(-m // bn) * bn, -(-n // bk) * bk)

    q = jnp.pad(queries, ((0, bp - b), (0, np_ - n)))
    lo = jnp.pad(lower, ((0, mp - m), (0, np_ - n)))
    hi = jnp.pad(upper, ((0, mp - m), (0, np_ - n)))

    nk = np_ // bk
    grid = (bp // bm, mp // bn, nk)
    _, _, s = pl.pallas_call(
        functools.partial(_kernel, nk=nk, alpha=alpha, n_true=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, mp), jnp.float32),  # D accumulator
            jax.ShapeDtypeStruct((bp, mp), jnp.float32),  # H accumulator
            jax.ShapeDtypeStruct((bp, mp), jnp.float32),  # S
        ],
        interpret=interpret,
    )(q.astype(jnp.float32), lo.astype(jnp.float32), hi.astype(jnp.float32))
    return s[:b, :m]


def _serve_kernel(f_ref, slot_ref, thr_ref, lo_ref, hi_ref, v_ref, wlo_ref,
                  whi_ref, tau_ref, d_ref, h_ref, pc_ref, pred_ref,
                  margin_ref, esc_ref, *, nj: int, nk: int, alpha: float,
                  n_true: int, num_k: int, cc: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        d_ref[...] = jnp.zeros_like(d_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    # per-slot tenant threshold row: one-hot MXU select from the resident
    # (T_pad, bk) thresholds-table block (exact — see acam_match._serve_kernel)
    slot = slot_ref[..., :1]
    t_pad = thr_ref.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (slot.shape[0], t_pad), 1)
    onehot = (iota == slot).astype(jnp.float32)
    thr = jax.lax.dot_general(
        onehot, thr_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # binarize against the gathered row; padded feature columns carry
    # f = -inf so q = 0 there, matching the zero-padded windows (counted as
    # hits, corrected in the chunk epilogue)
    q = jnp.where(f_ref[...] - thr > 0, 1.0, 0.0)[:, None, :]
    lo = lo_ref[...].reshape(num_k * cc, lo_ref.shape[-1])[None, :, :]
    hi = hi_ref[...].reshape(num_k * cc, hi_ref.shape[-1])[None, :, :]

    above = jnp.maximum(q - hi, 0.0)
    below = jnp.maximum(lo - q, 0.0)
    d_ref[...] += jnp.sum(above * above + below * below, axis=-1)
    hit = jnp.logical_and(q >= lo, q <= hi)
    h_ref[...] += jnp.sum(hit.astype(jnp.float32), axis=-1)

    @pl.when(k == nk - 1)
    def _chunk_epilogue():
        from repro.kernels.layout import store_class_chunk, windowed_margin

        pad_hits = float(nk * f_ref.shape[-1] - n_true)
        s = eq11(h_ref[...] - pad_hits, d_ref[...], n_true, alpha)
        vrow = v_ref[...].reshape(1, num_k * cc)
        s = jnp.where(vrow > 0, s, -jnp.inf)
        chunk_pc = s[:, :cc]
        for kk in range(1, num_k):
            chunk_pc = jnp.maximum(chunk_pc, s[:, kk * cc:(kk + 1) * cc])
        store_class_chunk(pc_ref, chunk_pc, j)

        @pl.when(j == nj - 1)
        def _final():
            pc = pc_ref[...]
            pred, margin = windowed_margin(pc, wlo_ref[..., :1],
                                           whi_ref[..., :1], 1.0)
            esc = (margin < tau_ref[..., 0]).astype(jnp.int32)
            pred_ref[...] = jnp.broadcast_to(pred[:, None], pred_ref.shape)
            margin_ref[...] = jnp.broadcast_to(margin[:, None],
                                               margin_ref.shape)
            esc_ref[...] = jnp.broadcast_to(esc[:, None], esc_ref.shape)


@functools.partial(jax.jit, static_argnames=("num_classes", "alpha", "chunk",
                                             "block", "interpret"))
def acam_similarity_serve(
        features: jax.Array, thr_table: jax.Array, tenant_slot: jax.Array,
        lower_kcp: jax.Array, upper_kcp: jax.Array, valid_kcp: jax.Array,
        class_lo: jax.Array, class_hi: jax.Array, tau: jax.Array,
        num_classes: int, *, alpha: float = 1.0, chunk: int,
        block=DEFAULT_BLOCK, interpret: bool = False
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Serving mega-kernel for the similarity method: gather -> binarize ->
    Eq. 9-11 window match -> per-class max -> windowed Eq. 12 margin ->
    escalation mask, in one dispatch at any bank size.

    Operand contract mirrors `acam_match.acam_match_serve`, with the bank as
    (K, Cp, N) lower/upper window stacks (`repro.kernels.layout.stack_kcp`).
    Margins are in Eq. 11 score units (cap 1.0). ``chunk`` class columns of
    all K window rows are VMEM-resident per grid step; D and H accumulate
    per chunk and the running per-class max crosses chunks in a revisited
    (bm, Cp) block. Returns (pred, per_class, margin, escalate).
    """
    b, n = features.shape
    num_k, cp, _ = lower_kcp.shape
    assert cp % chunk == 0, "chunk must divide the padded class count"
    t_rows = thr_table.shape[0]
    t_pad = -(-t_rows // 8) * 8
    bm, _, bk = block
    # the window compare broadcasts a (bm, K * chunk, bk) tile: shrink the
    # query rows per step if that would bust the VMEM budget
    while bm > 8 and bm * num_k * chunk * bk * 4 > 8 * 1024 * 1024:
        bm //= 2
    bp, np_ = (-(-b // bm) * bm, -(-n // bk) * bk)

    f = jnp.pad(features.astype(jnp.float32), ((0, bp - b), (0, np_ - n)),
                constant_values=-jnp.inf)
    thr = jnp.pad(thr_table.astype(jnp.float32),
                  ((0, t_pad - t_rows), (0, np_ - n)))
    lo = jnp.pad(lower_kcp.astype(jnp.float32), ((0, 0), (0, 0),
                                                 (0, np_ - n)))
    hi = jnp.pad(upper_kcp.astype(jnp.float32), ((0, 0), (0, 0),
                                                 (0, np_ - n)))
    slot = jnp.broadcast_to(
        jnp.pad(tenant_slot.astype(jnp.int32), (0, bp - b))[:, None],
        (bp, PRED_LANES))
    wlo = jnp.broadcast_to(
        jnp.pad(class_lo.astype(jnp.int32), (0, bp - b))[:, None],
        (bp, PRED_LANES))
    whi = jnp.broadcast_to(
        jnp.pad(class_hi.astype(jnp.int32), (0, bp - b))[:, None],
        (bp, PRED_LANES))
    tau_c = jnp.broadcast_to(
        jnp.pad(tau.astype(jnp.float32), (0, bp - b),
                constant_values=-jnp.inf)[:, None],
        (bp, PRED_LANES))

    nj = cp // chunk
    nk = np_ // bk
    grid = (bp // bm, nj, nk)
    _, _, per_class, pred, margin, esc = pl.pallas_call(
        functools.partial(_serve_kernel, nj=nj, nk=nk, alpha=alpha,
                          n_true=n, num_k=num_k, cc=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((t_pad, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((num_k, chunk, bk), lambda i, j, k: (0, j, k)),
            pl.BlockSpec((num_k, chunk, bk), lambda i, j, k: (0, j, k)),
            pl.BlockSpec((num_k, chunk), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, num_k * chunk), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, num_k * chunk), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, cp), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, num_k * cp), jnp.float32),  # D
            jax.ShapeDtypeStruct((bp, num_k * cp), jnp.float32),  # H
            jax.ShapeDtypeStruct((bp, cp), jnp.float32),  # running per-class
            jax.ShapeDtypeStruct((bp, PRED_LANES), jnp.int32),  # WTA index
            jax.ShapeDtypeStruct((bp, PRED_LANES), jnp.float32),  # margin
            jax.ShapeDtypeStruct((bp, PRED_LANES), jnp.int32),  # escalate
        ],
        interpret=interpret,
    )(f, slot, thr, lo, hi, valid_kcp, wlo, whi, tau_c)
    return (pred[:b, 0], per_class[:b, :num_classes], margin[:b, 0],
            esc[:b, 0].astype(bool))
