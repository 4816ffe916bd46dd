"""The plain reference of the served answer, in numpy, from the paper.

For a request of tenant t with raw features f (arXiv:2502.10089):

    q_i      = 1[f_i > thr_t,i]                       binarisation (Sec. II-C)
    S_ck     = sum_i 1[q_i == T_tck,i]                Eq. 8 feature count
    s_c      = max_k S_ck over valid templates        per-class score
    winner   = the first c with the largest s_c       Eq. 12 WTA, over the
                                                      tenant's own classes
    margin   = s_winner - max(max_{c != winner} s_c, s_winner - N)
    escalate = margin < tau                           the cascade
    head     = argmax_c (f . W_t[:, c] + b_t,c)       the dense escalation
                                                      head (logits in float64)

It imports nothing of the program: it reads the fleet `deploy.py` made from
the seed, not the super-bank, windows or tables the service built from it.

``precision="control"`` is the comparison's control: the same answers
computed one precision step below what the configuration states. The
compare is stated in float32, so the control rounds the features and
thresholds to bfloat16 first. The head runs at the TPU's default matmul
precision (bfloat16 multiplies, float32 sums), so the control rounds its
operands to fp8 e4m3's 3 mantissa bits (every value here lies inside e4m3's
range; its subnormals are not modelled).
"""
from __future__ import annotations

import numpy as np

BLOCK = 2048  # requests per block: bounds the (R, C, K, bytes) temporaries


BF16_BITS, E4M3_BITS = 7, 3  # explicit mantissa bits


def round_mantissa(x: np.ndarray, bits: int) -> np.ndarray:
    """float32 rounded to ``bits`` mantissa bits (to nearest, ties to even),
    returned as float32: 7 is bfloat16, 3 is fp8 e4m3."""
    drop = 23 - bits
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = u + np.uint32((1 << (drop - 1)) - 1) + ((u >> np.uint32(drop))
                                                 & np.uint32(1))
    return (u & np.uint32(0xFFFFFFFF ^ ((1 << drop) - 1))).view(np.float32)


def acam(fleet, tenant: np.ndarray, feats: np.ndarray, tau: float, *,
         precision: str = "float32"):
    """-> (winner (R,) tenant-local class, margin (R,) float, escalate
    (R,) bool) for requests of ``tenant`` (R,) with ``feats`` (R, N)."""
    n = feats.shape[1]
    packed = np.packbits(fleet.templates > 0.5, axis=-1)  # (T, C, K, B)
    winners, margins = [], []
    for s in range(0, len(tenant), BLOCK):
        t = tenant[s:s + BLOCK]
        f, thr = feats[s:s + BLOCK], fleet.thresholds[t]
        if precision == "control":
            f = round_mantissa(f, BF16_BITS)
            thr = round_mantissa(thr, BF16_BITS)
        q = np.packbits(f > thr, axis=-1)  # (R, B)
        miss = np.bitwise_count(q[:, None, None, :] ^ packed[t])
        counts = n - miss.sum(axis=-1, dtype=np.int64)  # (R, C, K)
        per_class = counts.max(axis=-1).astype(np.float64)  # all valid
        win = per_class.argmax(axis=-1)  # first of the tied maxima
        top1 = per_class[np.arange(len(t)), win]
        others = per_class.copy()
        others[np.arange(len(t)), win] = -np.inf
        top2 = np.maximum(others.max(axis=-1), top1 - n)
        winners.append(win)
        margins.append(top1 - top2)
    winner = np.concatenate(winners)
    margin = np.concatenate(margins)
    return winner, margin, margin < tau


def head_logits(fleet, tenant: np.ndarray, feats: np.ndarray, *,
                precision: str = "float32") -> np.ndarray:
    """(R, C) float64 logits of each request's tenant head."""
    out = []
    for s in range(0, len(tenant), BLOCK // 4):
        t = tenant[s:s + BLOCK // 4]
        f, w = feats[s:s + BLOCK // 4], fleet.head_w[t]  # (R, N), (R, N, C)
        if precision == "control":
            f, w = round_mantissa(f, E4M3_BITS), round_mantissa(w, E4M3_BITS)
        out.append(np.einsum("rn,rnc->rc", f.astype(np.float64),
                             w.astype(np.float64)) + fleet.head_b[t])
    return np.concatenate(out) if out else np.zeros((0, 0))


def answers(fleet, tenant, feats, tau: float, *, precision: str = "float32"):
    """Every part of the served answer: (winner, margin, escalate, pred),
    where pred is the head's class for escalated requests and the winner
    otherwise."""
    winner, margin, esc = acam(fleet, tenant, feats, tau, precision=precision)
    pred = winner.copy()
    if esc.any():
        pred[esc] = head_logits(fleet, tenant[esc], feats[esc],
                                precision=precision).argmax(axis=-1)
    return winner, margin, esc, pred
