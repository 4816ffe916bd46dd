"""Pallas TPU kernel: ACAM feature-count matching (paper Eq. 8).

TPU adaptation (DESIGN.md §4): the binary match count
    S_fc(Q, T) = sum_i 1(Q_i == T_i)
is a Hamming affinity. GPU implementations reach for XNOR/popcount; the TPU
has no popcount path that beats the MXU, but with bits encoded as +/-1 bf16:

    S_fc = (N + Q~ . T~^T) / 2,     Q~ = 2Q-1, T~ = 2T-1

— a plain matmul. The kernel fuses the *binarisation* (mean-threshold
compare, paper §II-C) and the bipolar encoding into the K-loop so the binary
feature map never round-trips to HBM, then runs an MXU-tiled matmul:

    grid = (B/bm, M/bn, N/bk)           (k innermost: VMEM accumulation)
    features block (bm, bk)  VMEM
    thresholds block (1, bk) VMEM
    templates block (bn, bk) VMEM       (stored {0,1}, encoded on the fly)
    out block (bm, bn) f32   VMEM accumulator

All block dims are multiples of (8, 128) so MXU/VREG tiling is aligned.
Block sizes are no longer hardcoded at the call sites: `repro.kernels.tuning`
resolves a tuned `(bm, bn, bk)` from its persistent JSON cache
(``$REPRO_TUNING_CACHE``, default ``~/.cache/repro/pallas_blocks.json``,
keyed ``kernel|backend|shape|dtype``) and falls back to `DEFAULT_BLOCK`.

Two entry points:

  `acam_match`        -> (B, M) match-count scores, behind the engine's
                         explicit `*_scores` entry points.
  `acam_match_serve`  -> the classify kernel: threshold-row gather ->
                         binarize -> match -> valid mask -> per-class max ->
                         WTA (Eq. 12) -> windowed winner-vs-runner-up margin
                         -> escalation mask, in one dispatch. The per-slot
                         tenant thresholds come from a (T, N) table by a
                         one-hot MXU select (exact under HIGHEST precision);
                         the margin is the confidence signal the hybrid
                         cascade thresholds to accept at the ACAM or
                         escalate to the CNN head. Templates arrive as a
                         (K, Cp, N) stack (`repro.kernels.layout.stack_kcp`)
                         and the grid walks the class dimension in
                         ``chunk``-column tiles, so only K * chunk template
                         rows sit in VMEM at a time; the chunk is the full
                         padded class count for banks inside the row budget
                         (`repro.match.MAX_FUSED_ROWS`), so one kernel
                         covers resident and class-chunked banks. A
                         single-tenant classify is its T = 1 face
                         (`ops.classify_fused_margins`).

The jnp references in `repro.match.backends` remain as oracles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (128, 128, 512)  # bm, bn, bk
PRED_LANES = 128  # WTA index output padded to one lane tile


def _kernel(f_ref, thr_ref, t_ref, o_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    f = f_ref[...]  # (bm, bk) raw features
    thr = thr_ref[...]  # (1, bk)
    t = t_ref[...]  # (bn, bk) binary {0,1} template

    q_pm = jnp.where(f > thr, 1.0, -1.0).astype(jnp.bfloat16)  # fused binarise
    t_pm = (2.0 * t - 1.0).astype(jnp.bfloat16)
    # MXU matmul on bipolar codes; f32 accumulate
    acc = jax.lax.dot_general(
        q_pm, t_pm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def acam_match(features: jax.Array, thresholds: jax.Array,
               templates: jax.Array, *, block=DEFAULT_BLOCK,
               interpret: bool = False) -> jax.Array:
    """Match scores (B, M): count of features agreeing with each template.

    features:   (B, N) float — raw front-end feature maps
    thresholds: (N,) float — per-feature binarisation thresholds
    templates:  (M, N) float {0, 1} — programmed ACAM point templates
    """
    b, n = features.shape
    m = templates.shape[0]
    bm, bn, bk = block
    bp, mp, np_ = (-(-b // bm) * bm, -(-m // bn) * bn, -(-n // bk) * bk)

    f = jnp.pad(features, ((0, bp - b), (0, np_ - n)))
    # pad thresholds with +inf so padded features binarise to -1 on BOTH the
    # query and (0-padded) template side: they agree, adding a constant that
    # cancels in the bipolar identity below.
    thr = jnp.pad(thresholds, (0, np_ - n), constant_values=jnp.inf)[None, :]
    t = jnp.pad(templates, ((0, mp - m), (0, np_ - n)))

    nk = np_ // bk
    grid = (bp // bm, mp // bn, nk)
    dot = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, mp), jnp.float32),
        interpret=interpret,
    )(f, thr, t)
    # bipolar identity: matches = (N_padded + dot)/2 minus the padded-column
    # contribution (pad columns always "match": (-1)*(-1)=+1), i.e. use the
    # true N in the correction term.
    scores = (np_ + dot[:b, :m]) * 0.5 - (np_ - n)
    return scores


def _serve_kernel(f_ref, slot_ref, thr_ref, t_ref, v_ref, lo_ref, hi_ref,
                  tau_ref, acc_ref, pc_ref, pred_ref, margin_ref, esc_ref, *,
                  nj: int, nk: int, n_true: int, num_k: int, cc: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-slot tenant threshold row, gathered IN the kernel: a one-hot MXU
    # select from the resident (T_pad, bk) thresholds-table block. Exact:
    # each output element sums exactly one table entry (1.0 * thr) plus
    # zeros, and HIGHEST precision keeps the f32 values unrounded — so
    # (f - thr) > 0 below reproduces the jnp take-then-shift composition
    # bit for bit.
    slot = slot_ref[..., :1]  # (bm, 1) payload column
    t_pad = thr_ref.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (slot.shape[0], t_pad), 1)
    onehot = (iota == slot).astype(jnp.float32)
    thr = jax.lax.dot_general(
        onehot, thr_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # per-tenant thresholds -> shared zero threshold (the scheduler's shift
    # trick, now in VMEM): binarize(f, thr_t) == (f - thr_t) > 0
    q_pm = jnp.where(f_ref[...] - thr > 0, 1.0, -1.0).astype(jnp.bfloat16)
    t = t_ref[...].reshape(num_k * cc, t_ref.shape[-1])
    t_pm = (2.0 * t - 1.0).astype(jnp.bfloat16)
    acc_ref[...] += jax.lax.dot_general(
        q_pm, t_pm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _chunk_epilogue():
        from repro.kernels.layout import store_class_chunk, windowed_margin

        np_ = float(nk * f_ref.shape[-1])
        scores = (np_ + acc_ref[...]) * 0.5 - (np_ - n_true)
        vrow = v_ref[...].reshape(1, num_k * cc)
        s = jnp.where(vrow > 0, scores, -jnp.inf)
        chunk_pc = s[:, :cc]
        for kk in range(1, num_k):
            chunk_pc = jnp.maximum(chunk_pc, s[:, kk * cc:(kk + 1) * cc])
        store_class_chunk(pc_ref, chunk_pc, j)

        @pl.when(j == nj - 1)
        def _final():
            pc = pc_ref[...]
            pred, margin = windowed_margin(pc, lo_ref[..., :1],
                                           hi_ref[..., :1], float(n_true))
            # the cascade's escalation mask: strictly below tau asks for the
            # CNN head; padding rows carry tau = -inf (never escalate)
            esc = (margin < tau_ref[..., 0]).astype(jnp.int32)
            pred_ref[...] = jnp.broadcast_to(pred[:, None], pred_ref.shape)
            margin_ref[...] = jnp.broadcast_to(margin[:, None],
                                               margin_ref.shape)
            esc_ref[...] = jnp.broadcast_to(esc[:, None], esc_ref.shape)


@functools.partial(jax.jit, static_argnames=("num_classes", "chunk", "block",
                                             "interpret"))
def acam_match_serve(
        features: jax.Array, thr_table: jax.Array, tenant_slot: jax.Array,
        templates_kcp: jax.Array, valid_kcp: jax.Array,
        class_lo: jax.Array, class_hi: jax.Array, tau: jax.Array,
        num_classes: int, *, chunk: int, block=DEFAULT_BLOCK,
        interpret: bool = False
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The serving mega-kernel: gather -> binarize -> match -> per-class
    max -> WTA -> windowed Eq. 12 margin -> escalation mask, in one
    dispatch over the multi-tenant super-bank.

    features:    (B, N) raw per-slot front-end feature maps (UNshifted)
    thr_table:   (T, N) per-tenant binarisation threshold rows
    tenant_slot: (B,) int32 — each row's tenant slot in ``thr_table``
    templates_kcp/valid_kcp: (K, Cp, N) / (K, Cp) super-bank stack
                 (`repro.kernels.layout.stack_kcp`)
    class_lo/class_hi: (B,) int32 tenant class windows (global indices)
    tau:         (B,) f32 cascade threshold; escalate = margin < tau
    chunk:       class columns per grid tile (`layout.class_chunk`) — equal
                 to Cp for banks inside the fused-row budget (nj == 1, the
                 fully resident case)

    Returns (pred (B,) int32 global class index, per_class (B, C) f32,
    margin (B,) f32, escalate (B,) bool). Rows with empty windows (slot
    padding) get pred 0 / margin 0, and padding rows ride tau = -inf so
    they never escalate.
    """
    b, n = features.shape
    num_k, cp, _ = templates_kcp.shape
    assert cp % chunk == 0, "chunk must divide the padded class count"
    t_rows = thr_table.shape[0]
    t_pad = -(-t_rows // 8) * 8  # sublane-align the thresholds table
    bm, _, bk = block
    bp, np_ = (-(-b // bm) * bm, -(-n // bk) * bk)

    # features pad with -inf (not 0): q = (f - thr) > 0 must binarise padded
    # columns to -1 for ANY thr, matching the 0-padded template bits; the
    # thresholds table itself pads with zeros so the one-hot select stays
    # finite (0 * inf would poison the MXU sum with NaN).
    f = jnp.pad(features, ((0, bp - b), (0, np_ - n)),
                constant_values=-jnp.inf)
    thr = jnp.pad(thr_table.astype(jnp.float32),
                  ((0, t_pad - t_rows), (0, np_ - n)))
    t = jnp.pad(templates_kcp, ((0, 0), (0, 0), (0, np_ - n)))
    # scalar per-row operands ride lane-aligned (B, PRED_LANES) carriers
    slot = jnp.broadcast_to(
        jnp.pad(tenant_slot.astype(jnp.int32), (0, bp - b))[:, None],
        (bp, PRED_LANES))
    lo = jnp.broadcast_to(
        jnp.pad(class_lo.astype(jnp.int32), (0, bp - b))[:, None],
        (bp, PRED_LANES))
    hi = jnp.broadcast_to(
        jnp.pad(class_hi.astype(jnp.int32), (0, bp - b))[:, None],
        (bp, PRED_LANES))
    tau_c = jnp.broadcast_to(
        jnp.pad(tau.astype(jnp.float32), (0, bp - b),
                constant_values=-jnp.inf)[:, None],
        (bp, PRED_LANES))

    nj = cp // chunk
    nk = np_ // bk
    grid = (bp // bm, nj, nk)
    _, per_class, pred, margin, esc = pl.pallas_call(
        functools.partial(_serve_kernel, nj=nj, nk=nk, n_true=n,
                          num_k=num_k, cc=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((t_pad, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((num_k, chunk, bk), lambda i, j, k: (0, j, k)),
            pl.BlockSpec((num_k, chunk), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, num_k * chunk), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, cp), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bm, PRED_LANES), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, num_k * cp), jnp.float32),
            jax.ShapeDtypeStruct((bp, cp), jnp.float32),  # running per-class
            jax.ShapeDtypeStruct((bp, PRED_LANES), jnp.int32),  # WTA index
            jax.ShapeDtypeStruct((bp, PRED_LANES), jnp.float32),  # margin
            jax.ShapeDtypeStruct((bp, PRED_LANES), jnp.int32),  # escalate
        ],
        interpret=interpret,
    )(f, slot, thr, t, valid_kcp, lo, hi, tau_c)
    return (pred[:b, 0], per_class[:b, :num_classes], margin[:b, 0],
            esc[:b, 0].astype(bool))
