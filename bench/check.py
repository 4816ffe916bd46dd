"""The comparison that decides ``correct``.

Once the window has closed, every request the end-to-end metrics count must
have an answer, and a sample of the answered requests, drawn from the seed,
is held against the plain reference (`reference.py`). The numbers compared,
each against the limit the configuration file gives it (``limits``):

    unanswered     requests of the window with no answer, or with an error
    acam_mismatch  sampled requests whose kernel winner, margin or
                   escalation bit differ from the reference's, or whose
                   accepted answer is not that winner (exact: limit 0)
    head_gap       over sampled escalated requests, the widest gap by which
                   the served class's reference logit lies below the
                   reference's best logit (0: every escalated answer is the
                   reference head's own)
"""
from __future__ import annotations

import numpy as np

import reference
from deploy import SAMPLE, rng

#: requests compared with the reference in each run, at most
SAMPLE_SIZE = 32768
NAMES = ("unanswered", "acam_mismatch", "head_gap")


def served(win, pool_size: int):
    """Answers of the requests the window counts: (pool index, winner,
    margin, escalate, pred) arrays of the answered ones, and the number
    without an answer."""
    rec = win.record
    ok = win.by_request(rec.ok, 0).astype(bool)
    i = np.frombuffer(rec.slot_rid, np.int64) - win.rid0
    keep = (i >= 0) & (i < win.population)
    winner = np.full(win.population, -1, np.int64)
    winner[i[keep]] = np.frombuffer(rec.slot_win, np.int64)[keep]
    has_winner = np.zeros(win.population, bool)
    has_winner[i[keep]] = True
    idx = np.flatnonzero(ok & has_winner)
    cols = (idx % pool_size, winner[idx], win.by_request(rec.margin, 0.0)[idx],
            win.by_request(rec.esc, 0)[idx].astype(bool),
            win.by_request(rec.pred, -1)[idx])
    return cols, win.population - len(idx)


def sample(n: int, seed: int) -> np.ndarray:
    """Indices of the answered requests to compare, drawn from the seed."""
    if n <= SAMPLE_SIZE:
        return np.arange(n)
    return np.sort(rng(seed, SAMPLE).choice(n, SAMPLE_SIZE, replace=False))


def numbers(fleet, pool, idx, got, tau: float) -> dict:
    """acam_mismatch and head_gap of served answers ``got`` = (winner,
    margin, escalate, pred) for pool entries ``idx``."""
    tenant, feats = pool.tenant[idx], pool.features[idx]
    winner, margin, esc = reference.acam(fleet, tenant, feats, tau)
    g_win, g_margin, g_esc, g_pred = (np.asarray(a) for a in got)
    bad = (g_win != winner) | (g_margin != margin) | (g_esc != esc)
    bad |= ~esc & (g_pred != winner)
    both = esc & g_esc
    gap = 0.0
    if both.any():
        logits = reference.head_logits(fleet, tenant[both], feats[both])
        pred = g_pred[both].astype(np.int64)
        ok = (pred >= 0) & (pred < logits.shape[1])
        if not ok.all():
            gap = float("inf")
        else:
            gap = float(np.max(logits.max(axis=1)
                               - logits[np.arange(len(pred)), pred]))
    return {"acam_mismatch": int(bad.sum()), "head_gap": gap,
            "checked": int(len(idx)), "escalated": int(esc.sum())}


def compare(cfg: dict, fleet, pool, win, seed: int) -> dict:
    """The window's numbers: {name: value}, plus the counts behind them."""
    (pidx, *got), unanswered = served(win, len(pool))
    pick = sample(len(pidx), seed)
    out = numbers(fleet, pool, pidx[pick], [a[pick] for a in got],
                  cfg["tau"]) if len(pidx) else {
        "acam_mismatch": 0, "head_gap": 0.0, "checked": 0, "escalated": 0}
    out["unanswered"] = unanswered
    return out


def control(cfg: dict, fleet, pool, win, seed: int) -> dict:
    """The control: the reference computed one precision step down, put in
    the program's place on the same sampled requests."""
    (pidx, *_), _ = served(win, len(pool))
    idx = pidx[sample(len(pidx), seed)]
    got = reference.answers(fleet, pool.tenant[idx], pool.features[idx],
                            cfg["tau"], precision="control")
    out = numbers(fleet, pool, idx, got, cfg["tau"])
    out["unanswered"] = 0
    return out


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}) over `NAMES`."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NAMES}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
