"""Template-bank layout shared by the ACAM serve kernels.

The bank is stored class-major ``(C, K, N)`` (class c, template k). The
serve kernels take it **stacked** ``(K, Cp, N)``: row ``[kk, c]`` holds
``bank[c, kk]``, with C padded up to ``Cp`` (a lane multiple, 128). A chunk
of ``cc`` class columns is then a contiguous (K, cc, N) block, and the
Eq. 12 per-class max of a chunk is

    per_class = max_kk scores[:, kk*cc : (kk+1)*cc]          # K static slices

— no strided gather. Padded class columns and invalid templates carry
``valid = 0`` and are driven to -inf before the max.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def padded_classes(num_classes: int, lane: int = LANE) -> int:
    return -(-num_classes // lane) * lane


def stack_kcp(arr: jax.Array, num_classes: int) -> jax.Array:
    """(C, K, N) -> (K, Cp, N), zero-padded: the 3D operand of the
    class-chunked serve kernels. Row [kk, c] = arr[c, kk]; a chunk of
    ``cc`` class columns is a contiguous (K, cc, N) block, so the kernel
    tiles the class dimension with a plain BlockSpec instead of keeping
    all K * Cp rows VMEM-resident."""
    c, k, n = arr.shape
    assert c == num_classes
    cp = padded_classes(c)
    return jnp.zeros((k, cp, n), arr.dtype).at[:, :c, :].set(
        jnp.swapaxes(arr, 0, 1))


def valid_kcp(valid: jax.Array, num_classes: int) -> jax.Array:
    """(C, K) bool -> (K, Cp) float {0,1}; padded classes are invalid."""
    c, k = valid.shape
    assert c == num_classes
    cp = padded_classes(c)
    return jnp.zeros((k, cp), jnp.float32).at[:, :c].set(
        jnp.swapaxes(valid.astype(jnp.float32), 0, 1))


def class_chunk(cp: int, num_k: int, max_rows: int, lane: int = LANE) -> int:
    """Class columns per chunk for the serve kernels: the largest
    lane-multiple divisor of ``Cp`` whose ``num_k * cc`` template rows fit
    the fused-row budget; ``lane`` when even one K-slice of a single lane
    tile exceeds it (the budget is a VMEM policy, not a hard limit)."""
    best = lane
    for units in range(cp // lane, 0, -1):
        cc = units * lane
        if cp % cc == 0 and num_k * cc <= max_rows:
            best = cc
            break
    return best


def store_class_chunk(pc_ref, chunk_pc: jax.Array, j) -> None:
    """Store chunk ``j``'s (bm, cc) per-class max into its columns of the
    (bm, Cp) per-class block, through the ref (Mosaic lowers no
    ``dynamic_update_slice`` on a loaded value). Chunk widths are lane
    multiples, so the dynamic column offset stays lane-aligned on TPU."""
    cc = chunk_pc.shape[-1]
    off = pl.multiple_of(j * cc, LANE)
    pc_ref[:, pl.ds(off, cc)] = chunk_pc


def first_argmax(x: jax.Array) -> jax.Array:
    """Index of the first maximum along the last axis, int32: the tie rule
    of `jnp.argmax` (lowest index), spelled out because Mosaic's argmax
    lowering does not keep it — on a TPU v5e, in-kernel `jnp.argmax` picked
    other tied classes than the jnp reference. A row of all -inf gives 0,
    as `jnp.argmax` does. Pure jnp, safe inside a Pallas kernel body."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    top = jnp.max(x, axis=-1, keepdims=True)
    return jnp.min(jnp.where(x == top, iota, x.shape[-1]), axis=-1)


def windowed_margin(per_class: jax.Array, class_lo: jax.Array,
                    class_hi: jax.Array, cap: float
                    ) -> tuple[jax.Array, jax.Array]:
    """Eq. 12 decision + winner-vs-runner-up margin inside a class window.

    The multi-tenant serving path stacks every tenant's classes into one
    super-bank; each request only competes within its tenant's contiguous
    class range ``[class_lo, class_hi)``. The margin is the confidence
    signal of the hybrid cascade (accept-at-ACAM vs escalate to the CNN
    head), clamped to ``cap`` (the score range: N for feature counts, 1 for
    similarities) so a single-valid-class window reads as fully confident
    instead of +inf.

    per_class: (bm, Cp) scores (-inf for invalid/padded classes)
    class_lo/class_hi: (bm, 1) int32 window bounds per row
    Returns (pred (bm,) int32 global class index, margin (bm,) f32).
    Rows with an empty window (lo == hi, e.g. batch padding) get pred 0,
    margin 0. Pure jnp, safe inside a Pallas kernel body.
    """
    bm, cp = per_class.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (bm, cp), 1)
    win = (iota >= class_lo) & (iota < class_hi)
    s = jnp.where(win, per_class, -jnp.inf)
    top1 = jnp.max(s, axis=-1)
    pred = first_argmax(s)
    runner = jnp.where(iota == pred[:, None], -jnp.inf, s)
    # clamp the runner-up at (top1 - cap): bounds the margin and keeps the
    # subtraction finite when the window holds a single valid class
    top2 = jnp.maximum(jnp.max(runner, axis=-1), top1 - cap)
    margin = jnp.where(jnp.isfinite(top1), top1 - top2, 0.0)
    return pred, margin.astype(jnp.float32)
