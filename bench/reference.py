"""Independent float64 reference: full-problem duality gaps over ALL of X.

Copied in substance from ``chip_smoke.py`` (``sgl_gaps`` / ``nn_gaps``)
and vectorised over rows: every returned row of every window call is held
to its own response in one pass over X, in column blocks so that a float64
copy of X is never made whole.  A feature the screening rules wrongly
discarded, a sweep that stopped short or a certificate that lied all show
up here as a gap above ``tol``.

SGL primal: ``0.5||y - Xb||^2 + lam (alpha sum_g sqrt(n_g)||b_g|| +
||b||_1)``.  The dual point is the residual over lam, scaled by the largest
s in (0, 1] with ``||S_1(s X_g^T r / lam)||_2 <= alpha sqrt(n_g)`` for
every group (bisection, so it is feasible).  For s <= 1 only entries with
``|x_j^T r| / lam > 1`` can violate that, so the bisection runs over those
entries alone.  Nonnegative Lasso: the dual point is r / lam scaled into
``{theta : X^T theta <= 1}``; a negative coefficient is infeasible (gap
inf).  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

BLOCK = 16384
BISECTIONS = 60


def _fit(X, B):
    """X @ b for every row b of B (M, p), touching only used columns."""
    nz = np.flatnonzero(np.any(B != 0.0, axis=0))
    return B[:, nz] @ X[:, nz].astype(np.float64).T


def _blocks(p: int, block: int):
    for c0 in range(0, p, block):
        yield c0, min(c0 + block, p)


def gap_ratios(X, Y, lams, B, tol, *, sizes=None, alpha=1.0,
               block: int = BLOCK) -> np.ndarray:
    """Per row m: f64 duality gap of B[m] at lams[m] for response Y[m],
    over ``tol * 0.5||Y[m]||^2`` (the engine's certificate scale).

    X (N, p) float32 or float64; Y (M, N); lams (M,); B (M, p).  ``sizes``
    (G,) gives contiguous SGL groups; None means nonnegative Lasso."""
    X = np.asarray(X)
    Y = np.asarray(Y, np.float64)
    lams = np.asarray(lams, np.float64)
    B = np.asarray(B, np.float64)
    M, p = B.shape
    bad = ~np.all(np.isfinite(B), axis=1)
    B = np.where(np.isfinite(B), B, 0.0)
    R = Y - _fit(X, B)
    Rl = R / lams[:, None]
    if sizes is None:
        bad |= np.any(B < 0.0, axis=1)
        m = np.full(M, -np.inf)
        for c0, c1 in _blocks(p, block):
            m = np.maximum(m, (Rl @ X[:, c0:c1].astype(np.float64)).max(1))
        s = np.where(m > 1.0, 1.0 / m, 1.0)
        pen = B.sum(axis=1)
    else:
        s, pen = _sgl_scaling(X, Rl, B, np.asarray(sizes), alpha, block)
    primal = 0.5 * np.sum(R * R, axis=1) + lams * pen
    d = Y - s[:, None] * R                      # y - lam * theta
    dual = 0.5 * np.sum(Y * Y, axis=1) - 0.5 * np.sum(d * d, axis=1)
    scale = tol * np.maximum(0.5 * np.sum(Y * Y, axis=1), 1e-30)
    ratios = (primal - dual) / scale
    return np.where(bad | ~np.isfinite(ratios), np.inf, ratios)


def _sgl_scaling(X, Rl, B, sizes, alpha, block):
    """(dual scaling s per row, SGL penalty per row)."""
    M, p = B.shape
    G = len(sizes)
    gid = np.repeat(np.arange(G), sizes)
    w = alpha * np.sqrt(sizes.astype(np.float64))
    rows, cols, vals = [], [], []
    for c0, c1 in _blocks(p, block):
        A = np.abs(Rl @ X[:, c0:c1].astype(np.float64))
        r, c = np.nonzero(A > 1.0)
        rows.append(r)
        cols.append(c + c0)
        vals.append(A[r, c])
    rows, cols, vals = (np.concatenate(v) for v in (rows, cols, vals))
    pair, inv = np.unique(rows * G + gid[cols], return_inverse=True)
    pair_row, pair_w = pair // G, w[pair % G]

    def feasible(s):
        sh = np.maximum(s[rows] * vals - 1.0, 0.0)
        ok = np.sqrt(np.bincount(inv, sh * sh, len(pair))) <= pair_w
        return np.bincount(pair_row[~ok], minlength=M) == 0

    lo, hi = np.zeros(M), np.ones(M)
    done = feasible(hi)
    for _ in range(BISECTIONS):
        mid = np.where(done, 1.0, 0.5 * (lo + hi))
        ok = feasible(mid)
        lo, hi = np.where(ok, mid, lo), np.where(ok, hi, mid)
    nz = np.flatnonzero(np.any(B != 0.0, axis=0))
    key = (np.arange(M)[:, None] * G + gid[nz][None, :]).ravel()
    gnorm = np.sqrt(np.bincount(key, (B[:, nz] ** 2).ravel(),
                                M * G)).reshape(M, G)
    pen = gnorm @ w + np.abs(B).sum(axis=1)
    return lo, pen
