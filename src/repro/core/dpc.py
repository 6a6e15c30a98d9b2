"""DPC screening for nonnegative Lasso (paper Section 5).

Dual feasible set is F = { theta : <x_i, theta> <= 1 } (Thm 19); the
decomposition C_1 = B_inf + R_-^p (Remark 4) makes feasibility explicit.
Theorem 20 gives lambda_max = max_i <x_i, y> (signed — not absolute value!),
Theorem 21 the normal-cone dual ball, Theorem 22 the DPC rule:

    <x_i, o> + r * ||x_i|| < 1   =>   beta_i* = 0.
"""
from __future__ import annotations

import jax.numpy as jnp

from .estimation import DualBall, estimate_dual_ball
from .linalg import mm


def lambda_max_nn(xty: jnp.ndarray):
    """(lambda_max, argmax feature) — Theorem 20(iv)."""
    return jnp.max(xty), jnp.argmax(xty)


def nn_dual_feasible(xt_theta: jnp.ndarray, tol: float = 0.0):
    return jnp.all(xt_theta <= 1.0 + tol)


def nn_dual_objective(y, theta, lam):
    d = y - lam * theta
    return 0.5 * jnp.vdot(y, y) - 0.5 * jnp.vdot(d, d)


def nn_primal_objective(X, y, beta, lam):
    r = y - mm(X, beta)
    return 0.5 * jnp.vdot(r, r) + lam * jnp.sum(beta)   # beta >= 0 => l1 = sum


def normal_vector_nn(X, y, lam_bar, lam_max, theta_bar, i_star) -> jnp.ndarray:
    """n(lam_bar) of Theorem 21: x_* at lam_max, else y/lam_bar - theta_bar."""
    at_max = jnp.asarray(lam_bar >= lam_max * (1.0 - 1e-12))
    return jnp.where(at_max, X[:, i_star], y / lam_bar - theta_bar)


def dpc_screen(X, ball: DualBall, col_norms, safety: float = 0.0):
    """Theorem 22.  Returns feat_keep (p,) bool: False => certified zero."""
    r = ball.radius * (1.0 + safety)
    omega = mm(X.T, ball.center) + r * col_norms
    return omega >= 1.0


def dpc_screen_grid(X, y, lambdas, theta_bar, n_vec, col_norms,
                    safety: float = 0.0, use_pallas: bool = False):
    """Theorem 22 for a WHOLE remaining lambda grid in one GEMM.

    Same center/radius algebra as the SGL grid rule (Theorem 21 shares the
    Theorem 12 geometry); returns (feat_keep (L, p), radii (L,)).
    ``use_pallas`` fuses the threshold through the fold-stack kernel as a
    one-fold stack (float32 only, as ``dpc_screen_grid_folds``)."""
    from .screening import _require_f32_for_pallas, grid_ball_geometry
    centers, radii = grid_ball_geometry(y, lambdas, theta_bar, n_vec)
    radii = radii * (1.0 + safety)
    C = mm(centers, X)
    if use_pallas:
        _require_f32_for_pallas(C.dtype)
        from ..kernels import ops as _kops
        return _kops.dpc_screen_folds(C[None], radii[None],
                                      col_norms[None])[0], radii
    omega = C + radii[:, None] * col_norms[None, :]
    return omega >= 1.0, radii


def dpc_screen_grid_folds(X, Y, lambdas, Theta_bar, N_vecs, col_norms_f,
                          safety: float = 0.0, use_pallas: bool = False):
    """Fold-batched Theorem 22: K folds x L lambdas in ONE GEMM.

    Same masked-row convention as ``screening.tlfre_screen_grid_folds``:
    per-fold vectors are (K, N) with held-out rows zeroed, ``lambdas`` is
    (K, L), ``col_norms_f`` (K, p).  (No centering support here — per-fold
    centering is an SGL-only feature; centering X breaks the nonnegativity
    geometry.)  ``use_pallas`` fuses the post-GEMM threshold
    ``C + r ||x_i|| >= 1`` into one streaming pass over the (K*L, p) layout
    (float32 only — float64 exactness runs refuse the kernel route).
    Returns (feat_keep (K, L, p), radii (K, L))."""
    from .screening import _require_f32_for_pallas, grid_ball_geometry_folds
    K, L = lambdas.shape
    N = Y.shape[1]
    centers, radii = grid_ball_geometry_folds(Y, lambdas, Theta_bar, N_vecs)
    radii = radii * (1.0 + safety)
    C = mm(centers.reshape(K * L, N), X).reshape(K, L, X.shape[1])
    if use_pallas:
        _require_f32_for_pallas(C.dtype)
        from ..kernels import ops as _kops
        return _kops.dpc_screen_folds(C.astype(jnp.float32),
                                      radii.astype(jnp.float32),
                                      col_norms_f.astype(jnp.float32)), radii
    omega = C + radii[:, :, None] * col_norms_f[:, None, :]
    return omega >= 1.0, radii


def gap_safe_screen_grid_nn(c_theta, radii, col_norms):
    """Gap-Safe DPC grid rules for a fixed feasible center: one GEMV, radii
    vary per lambda.  Returns feat_keep (L, p)."""
    omega = c_theta[None, :] + radii[:, None] * col_norms[None, :]
    return omega >= 1.0


# ---------------------------------------------------------------------------
# Feature-sharded Theorem-22 screens (see core.screening for the SGL
# counterparts and distributed.feature_shard for the executor / layout).
# The threshold is per-column, so the sharded rule is the unsharded rule on
# each block; pad columns give omega = 0 < 1 and are never kept.
# ---------------------------------------------------------------------------

def dpc_screen_grid_feat(ops, Xs, y, lambdas, theta_bar, n_vec,
                         col_norms_s, safety: float = 0.0):
    """Sharded ``dpc_screen_grid``: returns (feat_keep (S, L, p_shard),
    radii (L,))."""
    from .screening import grid_ball_geometry
    centers, radii = grid_ball_geometry(y, lambdas, theta_bar, n_vec)
    radii = radii * (1.0 + safety)

    def body(loc, centers, radii):
        Xb, cn = loc
        omega = mm(centers, Xb) + radii[:, None] * cn[None, :]
        return omega >= 1.0

    return ops.fmap(body, (Xs, col_norms_s), centers, radii), radii


def dpc_screen_grid_folds_feat(ops, Xs, Y, lambdas, Theta_bar, N_vecs,
                               col_norms_sf, safety: float = 0.0):
    """Sharded ``dpc_screen_grid_folds`` (jnp route only — the fused
    fold-stack kernel stays a single-device feature).  Returns
    (feat_keep (S, K, L, p_shard), radii (K, L))."""
    from .screening import grid_ball_geometry_folds
    K, L = lambdas.shape
    N = Y.shape[1]
    centers, radii = grid_ball_geometry_folds(Y, lambdas, Theta_bar, N_vecs)
    radii = radii * (1.0 + safety)

    def body(loc, centers, radii):
        Xb, cn = loc
        C = mm(centers.reshape(K * L, N), Xb).reshape(K, L,
                                                         Xb.shape[1])
        omega = C + radii[:, :, None] * cn[:, None, :]
        return omega >= 1.0

    return ops.fmap(body, (Xs, col_norms_sf), centers, radii), radii


def gap_safe_screen_grid_nn_feat(ops, c_theta_s, radii, col_norms_s):
    """Sharded ``gap_safe_screen_grid_nn``: stacked fixed center
    ``c_theta_s`` (S, p_shard).  Returns feat_keep (S, L, p_shard)."""
    def body(loc, radii):
        ct, cn = loc
        return gap_safe_screen_grid_nn(ct, radii, cn)

    return ops.fmap(body, (c_theta_s, col_norms_s), radii)


def dual_scaling_nn(xt_rho: jnp.ndarray):
    """Largest s in (0,1] with s * rho dual-feasible for (82)."""
    m = jnp.max(xt_rho)
    return jnp.where(m > 1.0, 1.0 / m, 1.0)


def estimate_dual_ball_nn(y, lam, lam_bar, theta_bar, n_vec) -> DualBall:
    """Theorem 21(ii) — same algebra as Theorem 12(ii)."""
    return estimate_dual_ball(y, lam, lam_bar, theta_bar, n_vec)
