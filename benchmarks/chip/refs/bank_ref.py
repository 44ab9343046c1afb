"""Plain float64 references for the bank cells.

``alg1_ref``, the fit and ovr comparisons and ``rbf_scores_ref`` are copied
from ``chip_smoke.py``, where they were checked against the chip; the
benchmark keeps its own copy so that a change to the program cannot move
the yardstick. Nothing here imports the program.

The tolerances ``chip_smoke.py`` used, with their reasons, are kept below as
a record. The benchmark's limits are not these: each cell's limits are set
from measured readings and live in ``limits/<workload>.json``.

- Centers and radii, relative to the float64 reference: the engine runs
  the recursion in f32 in another order (block Gram + rank-1 updates vs.
  direct distances), so rounding differs by a few ulps per row and
  accumulates over the absorbed rows: about 5e-7 on a v5e. ``chip_smoke``
  allowed 1e-4, which still fails a dot rounded through one bf16 pass
  (about 2e-3).
- Core-vector counts: an update at d ~ r has step s = (1 - r/d)/2 ~ 0, so
  a decision that rounding flips changes m by one and the center by almost
  nothing (``chip_smoke`` allowed 1% of m, plus 2).
- Served margins, relative to ||x|| * max_b ||w_b||: an f32 dot of D <= 4096
  terms rounds within ~sqrt(D) * 2**-24 of that scale (~4e-6 at D = 784);
  ``chip_smoke`` allowed 1e-5, which would catch a single bf16 pass (~4e-3).
- Kernel-bank served scores, relative to sum_s |coef|: each score is
  sum_s coef_s * k_s with k in [0, 1], and each RBF value carries the f32
  rounding of its exponent (``chip_smoke`` allowed 1e-5).
"""
from __future__ import annotations

import numpy as np


def alg1_ref(X, Y, cs):
    """Algorithm 1 (exact slack gain) in float64 numpy, models vectorized,
    rows in stream order; row 0 seeds every model (the engine's init).

    Returns (w, r, xi2, m)."""
    X = np.asarray(X, np.float64)
    Y = np.asarray(Y, np.float64)
    c_inv = 1.0 / np.asarray(cs, np.float64)
    w = Y[:, :1] * X[0][None, :]
    r = np.zeros(len(c_inv))
    xi2 = c_inv.copy()
    m = np.ones(len(c_inv), np.int64)
    for i in range(1, X.shape[0]):
        row = Y[:, i : i + 1] * X[i][None, :]
        d = np.sqrt(np.maximum(
            np.sum((w - row) ** 2, axis=1) + xi2 + c_inv, 1e-12
        ))
        upd = (d >= r) & (Y[:, i] != 0)
        if not upd.any():
            continue
        s = np.where(upd, 0.5 * (1.0 - r / d), 0.0)
        w = (1.0 - s)[:, None] * w + s[:, None] * row
        r = np.where(upd, r + 0.5 * (d - r), r)
        xi2 = xi2 * (1.0 - s) ** 2 + s**2 * c_inv
        m += upd
    return w, r, xi2, m


def alg1_blocked(X, Y, cs, *, first_block=8, max_block=4096):
    """``alg1_ref``, skipping runs of rows that no model absorbs.

    The same recursion in float64: rows are tested a block at a time
    against the current centers (one matrix product), the first row that
    any model absorbs is applied with ``alg1_ref``'s own arithmetic, and
    the scan resumes after it. The block grows while no model absorbs and
    starts small again after an absorb. X: (n, D), Y: (k, n) signs (any
    float or int dtype; X may be float32 and is widened a block at a time).
    """
    n = X.shape[0]
    c_inv = 1.0 / np.asarray(cs, np.float64)
    sign0 = np.asarray(Y[:, 0], np.float64)
    w = sign0[:, None] * np.asarray(X[0], np.float64)[None, :]
    r = np.zeros(len(c_inv))
    xi2 = c_inv.copy()
    m = np.ones(len(c_inv), np.int64)
    wsq = np.sum(w * w, axis=1)
    i, size = 1, first_block
    while i < n:
        end = min(n, i + size)
        blk = np.asarray(X[i:end], np.float64)
        ys = np.asarray(Y[:, i:end], np.float64)
        xsq = np.einsum("kd,kd->k", blk, blk)
        d2 = (wsq + xi2 + c_inv)[:, None] - 2.0 * ys * (w @ blk.T) + xsq[None]
        viol = (np.sqrt(np.maximum(d2, 1e-12)) >= r[:, None]) & (ys != 0)
        hit = viol.any(axis=0)
        if not hit.any():
            i, size = end, min(2 * size, max_block)
            continue
        j = int(np.argmax(hit))
        y = ys[:, j]
        row = y[:, None] * blk[j][None, :]
        d = np.sqrt(np.maximum(
            np.sum((w - row) ** 2, axis=1) + xi2 + c_inv, 1e-12
        ))
        upd = (d >= r) & (y != 0)
        s = np.where(upd, 0.5 * (1.0 - r / d), 0.0)
        w = (1.0 - s)[:, None] * w + s[:, None] * row
        r = np.where(upd, r + 0.5 * (d - r), r)
        xi2 = xi2 * (1.0 - s) ** 2 + s**2 * c_inv
        m += upd
        wsq = np.sum(w * w, axis=1)
        i, size = i + j + 1, first_block
    return w, r, xi2, m


def merge_ref(a, b):
    """Smallest ball enclosing two balls of disjoint example sets (the
    paper's Sec. 4.3 merge), per model, in float64. a, b: (w, r, xi2, m)."""
    w1, r1, x1, m1 = a
    w2, r2, x2, m2 = b
    dist = np.sqrt(np.maximum(np.sum((w1 - w2) ** 2, axis=1) + x1 + x2, 0.0))
    one_in_two = dist + r1 <= r2
    two_in_one = dist + r2 <= r1
    r_join = 0.5 * (r1 + r2 + dist)
    t = np.clip((r_join - r1) / np.maximum(dist, 1e-12), 0.0, 1.0)
    w_join = w1 + t[:, None] * (w2 - w1)
    x_join = (1.0 - t) ** 2 * x1 + t**2 * x2
    pick = lambda v2, v1, vj: np.where(
        one_in_two, v2, np.where(two_in_one, v1, vj)
    )
    w = np.where(one_in_two[:, None], w2, np.where(two_in_one[:, None], w1,
                                                   w_join))
    return w, pick(r2, r1, r_join), pick(x2, x1, x_join), m1 + m2


def shard_bounds(n: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous ceil-split ranges of ``n`` rows over ``n_shards``."""
    per = -(-n // n_shards)
    return [(min(j * per, n), min((j + 1) * per, n)) for j in range(n_shards)]


def sharded_ref(X, Y, cs, n_shards: int, fit=alg1_blocked, workers=None):
    """One fit per contiguous stream range, folded left to right in order
    (a single range is a plain one-pass fit). Ranges run in threads: numpy
    releases the interpreter lock inside its products."""
    ranges = [(lo, hi) for lo, hi in shard_bounds(X.shape[0], n_shards)
              if hi > lo]
    run = lambda lh: fit(X[lh[0]:lh[1]], Y[:, lh[0]:lh[1]], cs)
    if len(ranges) == 1:
        return run(ranges[0])
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=workers or len(ranges)) as pool:
        banks = list(pool.map(run, ranges))
    out = banks[0]
    for bank in banks[1:]:
        out = merge_ref(out, bank)
    return out


def fit_errors(got, ref) -> dict:
    """Worst model's gap between a trained bank and its reference.

    got, ref: (w, r, xi2, m) for the same models. Centers by the norm of the
    difference over the reference's norm; radius, slack and core-vector
    count relative to the reference's value."""
    w, r, xi2, m = (np.asarray(v, np.float64) for v in got)
    w_r, r_r, xi2_r, m_r = (np.asarray(v, np.float64) for v in ref)
    rel = lambda a, b: float(np.max(np.abs(a - b) / np.abs(b)))
    return {
        "center_err": float(np.max(
            np.linalg.norm(w - w_r, axis=1) / np.linalg.norm(w_r, axis=1)
        )),
        "radius_err": rel(r, r_r),
        "slack_err": rel(xi2, xi2_r),
        "count_err": rel(m, m_r),
    }


def ovr_ref(Xq, W, n_classes):
    """Float64 ovr readout: per C-grid group, the winning class, its margin
    and the runner-up's margin. Xq: (q, D), W: (B, D) class-major groups."""
    S = np.asarray(Xq, np.float64) @ np.asarray(W, np.float64).T
    G = S.reshape(S.shape[0], -1, n_classes)
    top2 = np.sort(G, axis=-1)[..., -2:]
    return np.argmax(G, axis=-1), top2[..., 1], top2[..., 0]


def ovr_errors(cls, margin, Xq, W, n_classes, decided: float) -> dict:
    """Served ovr (class, margin) against the float64 readout of the same
    bank: the worst margin gap relative to ||x|| max_b ||w_b||, and how many
    classes differ where the reference's winner leads its runner-up by more
    than ``decided`` of that scale (near-ties are exempt: rounding may pick
    either)."""
    rcls, top1, top2 = ovr_ref(Xq, W, n_classes)
    scale = (
        np.linalg.norm(np.asarray(Xq, np.float64), axis=1)[:, None]
        * np.linalg.norm(np.asarray(W, np.float64), axis=1).max()
    )
    gap = np.abs(np.asarray(margin, np.float64) - top1) / scale
    is_decided = (top1 - top2) / scale > decided
    wrong = (np.asarray(cls) != rcls) & is_decided
    return {
        "margin_err": float(np.max(gap)) if gap.size else 0.0,
        "wrong_classes": float(np.sum(wrong)),
    }


def topk_errors(vals, ids, Xq, W, k: int, decided: float) -> dict:
    """Served top-k (scores, model ids) against the float64 readout of the
    same bank: the worst gap between the j-th served score and the j-th
    best reference score, relative to ||x|| max_b ||w_b||, and how many ids
    differ at ranks whose reference score leads the next and trails the one
    before by more than ``decided`` of that scale (near-ties are exempt)."""
    S = np.asarray(Xq, np.float64) @ np.asarray(W, np.float64).T
    order = np.argsort(-S, axis=1, kind="stable")[:, : k + 1]
    ref = np.take_along_axis(S, order, axis=1)
    scale = (
        np.linalg.norm(np.asarray(Xq, np.float64), axis=1)[:, None]
        * np.linalg.norm(np.asarray(W, np.float64), axis=1).max()
    )
    gap = np.abs(np.asarray(vals, np.float64) - ref[:, :k]) / scale
    after = (ref[:, :k] - ref[:, 1 : k + 1]) / scale
    before = np.concatenate(
        [np.full((len(S), 1), np.inf), -np.diff(ref[:, :k], axis=1)], axis=1
    ) / scale
    is_decided = (after > decided) & (before > decided)
    wrong = (np.asarray(ids) != order[:, :k]) & is_decided
    return {
        "score_err": float(np.max(gap)) if gap.size else 0.0,
        "wrong_ids": float(np.sum(wrong)),
    }


def rbf_scores_ref(Xq, points, coef, gamma):
    """sum_s coef[b, s] exp(-gamma |x - p_bs|^2) in float64 numpy."""
    Xq = np.asarray(Xq, np.float64)
    P = np.asarray(points, np.float64)
    d2 = (
        np.sum(Xq**2, 1)[:, None, None]
        + np.sum(P**2, -1)[None]
        - 2.0 * np.einsum("qd,bsd->qbs", Xq, P)
    )
    K = np.exp(-gamma * np.maximum(d2, 0.0))
    return np.einsum("qbs,bs->qb", K, np.asarray(coef, np.float64))
