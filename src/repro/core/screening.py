"""TLFre: the two-layer screening rules (paper Theorems 15, 16, 17).

Layer 1 (group):    s_g* < alpha*w_g                        => beta_g* = 0
Layer 2 (feature):  |x_i^T o| + r*||x_i||_2 <= 1            => beta_i* = 0

where ``o``/``r`` are the dual-ball center/radius from Theorem 12 (or the
beyond-paper Gap-Safe ball) and s_g* is the closed-form sup of the nonconvex
program sup{ ||S_1(xi)|| : ||xi - c_g|| <= r_g } of Theorem 15:

    ||c||_inf >= 1 :  s* = ||S_1(c)|| + r
    ||c||_inf <  1 :  s* = (||c||_inf + r - 1)_+

(the boundary case ||c||_inf == 1 is the continuous limit of both branches).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .estimation import DualBall, project_out_normal
from .fenchel import shrink
from .groups import (GroupSpec, broadcast_to_features, group_max_abs,
                     group_norms, pad_slots)
from .linalg import mm


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ScreenResult:
    group_keep: jnp.ndarray    # (G,) bool — False => group certified zero (L1)
    feat_keep: jnp.ndarray     # (p,) bool — False => feature certified zero (L1|L2)
    s_sup: jnp.ndarray         # (G,) the Theorem-15 sup values
    t_sup: jnp.ndarray         # (p,) the Theorem-16 sup values

    def tree_flatten(self):
        return (self.group_keep, self.feat_keep, self.s_sup, self.t_sup), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def sup_shrink_norm(c_shrink_norm, c_inf, r):
    """Theorem 15 closed form, branch-free."""
    return jnp.where(c_inf >= 1.0,
                     c_shrink_norm + r,
                     jnp.maximum(c_inf + r - 1.0, 0.0))


def tlfre_screen(X, spec: GroupSpec, alpha, ball: DualBall,
                 col_norms: jnp.ndarray, group_specnorms: jnp.ndarray,
                 safety: float = 0.0) -> ScreenResult:
    """Apply (L1) and (L2) given a dual ball.

    col_norms: (p,) column l2 norms of X;  group_specnorms: (G,) ||X_g||_2
    spectral norms.  ``safety`` inflates the ball radius multiplicatively (use
    a few ULPs when running in float32; exactness tests use 0 under float64).
    """
    r = ball.radius * (1.0 + safety)
    c = mm(X.T, ball.center)                    # (p,)  — the screening GEMV
    if spec.feature_weights is None:
        shr = shrink(c)
        c_norm = group_norms(spec, shr)
        c_inf = group_max_abs(spec, c)
        s = sup_shrink_norm(c_norm, c_inf, r * group_specnorms)  # (G,)
        l2_thresh = 1.0
    else:
        # adaptive l1: the exact Theorem-15 sup has no weighted closed form;
        # S_w is 1-Lipschitz, so ||S_w(c)|| + r is a safe (conservative) sup
        w = spec.feature_weights.astype(c.dtype)
        s = group_norms(spec, shrink(c, w)) + r * group_specnorms
        l2_thresh = w
    group_keep = s >= alpha * spec.weights                       # (L1)

    t = jnp.abs(c) + r * col_norms                               # (p,) Thm 16
    feat_keep = t > l2_thresh                                    # (L2)
    feat_keep = feat_keep & broadcast_to_features(spec, group_keep)
    return ScreenResult(group_keep, feat_keep, s, t)


def screen_stats(spec: GroupSpec, res: ScreenResult):
    """(#groups discarded, #features discarded by L1, #extra features by L2)."""
    g_drop = jnp.sum(~res.group_keep)
    feats_in_dropped = jnp.sum(jnp.where(
        ~broadcast_to_features(spec, res.group_keep), 1, 0))
    l2_extra = jnp.sum((~res.feat_keep) &
                       broadcast_to_features(spec, res.group_keep))
    return g_drop, feats_in_dropped, l2_extra


def _require_f32_for_pallas(dtype) -> None:
    """The Pallas kernels compute in float32; silently round-tripping a
    float64 exactness run through them would destroy the screening-rule
    proofs.  Raise at trace time instead (the engines gate kernel use via
    ``_pallas_active``, which never engages them for float64)."""
    if dtype == jnp.float64:
        raise TypeError(
            "use_pallas=True would round-trip float64 screening statistics "
            "through the float32 Pallas kernels; float64 exactness runs "
            "must use the jnp path (use_pallas=False)")


def _grid_group_stats(spec: GroupSpec, C: jnp.ndarray, use_pallas: bool):
    """(||S_1(C_g)||, ||C_g||_inf) per grid row: (L, p) -> ((L, G), (L, G)).

    ``use_pallas`` routes the fused reduction through the ``screen_norms``
    kernel on the slot-major (n_max, L, G) layout (float32 — callers must
    carry a nonzero ``safety`` inflation; the float64 exactness path keeps
    the jnp segment reductions and float64 inputs refuse the kernel route).
    """
    if use_pallas:
        _require_f32_for_pallas(C.dtype)
        from ..kernels import ops as _kops
        snorm2, cinf = _kops.screen_norms_batched(
            pad_slots(spec, C.astype(jnp.float32)), spec.pad_mask.T)
        return jnp.sqrt(snorm2).astype(C.dtype), cinf.astype(C.dtype)
    c_norm = jax.vmap(lambda r: group_norms(spec, r))(shrink(C))   # (L, G)
    c_inf = jax.vmap(lambda r: group_max_abs(spec, r))(jnp.abs(C))
    return c_norm, c_inf


def _grid_group_stats_folds(spec: GroupSpec, C: jnp.ndarray,
                            use_pallas: bool):
    """Fold-stacked group statistics: (K, L, p) -> ((K, L, G), (K, L, G)).

    ``use_pallas`` routes the whole (K*L, p) CV layout through ONE fused
    ``screen_norms_folds`` kernel launch (float32, same f64 refusal as
    ``_grid_group_stats``); the fallback vmaps the jnp segment reductions
    over the fold axis."""
    if use_pallas:
        _require_f32_for_pallas(C.dtype)
        from ..kernels import ops as _kops
        snorm2, cinf = _kops.screen_norms_folds(
            pad_slots(spec, C.astype(jnp.float32)), spec.pad_mask.T)
        return jnp.sqrt(snorm2).astype(C.dtype), cinf.astype(C.dtype)
    return jax.vmap(lambda Ck: _grid_group_stats(spec, Ck, False))(C)


def _grid_rules(spec: GroupSpec, alpha, C, radii, col_norms, group_specnorms,
                use_pallas: bool = False):
    """Theorems 15/16 evaluated for every (lambda, group/feature) pair.

    With adaptive per-feature weights the exact Theorem-15 sup has no
    weighted closed form; ``S_w`` is 1-Lipschitz, so ``||S_w(c)|| + r`` is a
    safe (conservative) sup and the feature threshold becomes ``w_f``.  The
    unweighted branch is the literal pre-adaptive code (bit-identical
    graphs; the Pallas stats route only exists there)."""
    r_g = radii[:, None] * group_specnorms[None, :]
    if spec.feature_weights is None:
        c_norm, c_inf = _grid_group_stats(spec, C, use_pallas)
        s = sup_shrink_norm(c_norm, c_inf, r_g)
        group_keep = s >= alpha * spec.weights[None, :]

        t = jnp.abs(C) + radii[:, None] * col_norms[None, :]
        feat_keep = (t > 1.0) & group_keep[:, spec.group_ids]
        return group_keep, feat_keep
    w = spec.feature_weights.astype(C.dtype)
    c_norm = jax.vmap(lambda row: group_norms(spec, shrink(row, w)))(C)
    s = c_norm + r_g
    group_keep = s >= alpha * spec.weights[None, :]

    t = jnp.abs(C) + radii[:, None] * col_norms[None, :]
    feat_keep = (t > w[None, :]) & group_keep[:, spec.group_ids]
    return group_keep, feat_keep


def grid_ball_geometry(y, lambdas, theta_bar, n_vec):
    """Theorem-12 ball centers/radii for a whole grid sharing (theta_bar, n).

    Returns (centers (L, N), radii (L,)) — the radii are NOT safety-inflated.
    """
    lambdas = jnp.asarray(lambdas)
    v = y[None, :] / lambdas[:, None] - theta_bar[None, :]        # (L, N)
    v_perp = project_out_normal(v, n_vec)   # shared zero-normal guard
    centers = theta_bar[None, :] + 0.5 * v_perp                   # (L, N)
    radii = 0.5 * jnp.linalg.norm(v_perp, axis=1)
    return centers, radii


def tlfre_screen_grid(X, y, spec: GroupSpec, alpha, lambdas, lam_bar,
                      theta_bar, n_vec, col_norms, group_specnorms,
                      safety: float = 0.0, use_pallas: bool = False):
    """Beyond-paper: evaluate the TLFre rules for a WHOLE remaining lambda
    grid at once (path engine / cross-validation / stability selection).

    The paper screens one lambda at a time; the dominant cost is the
    screening GEMV X^T o.  All grid points share theta_bar, so their ball
    centers differ only along y and v_perp — stacking them turns L GEMVs
    into ONE (L, N) x (N, p) GEMM, which is the MXU-shaped formulation.

    Returns (group_keep (L, G), feat_keep (L, p), radii (L,)).
    """
    centers, radii = grid_ball_geometry(y, lambdas, theta_bar, n_vec)
    radii = radii * (1.0 + safety)
    C = mm(centers, X)                                             # (L, p)
    group_keep, feat_keep = _grid_rules(spec, alpha, C, radii, col_norms,
                                        group_specnorms, use_pallas)
    return group_keep, feat_keep, radii


def grid_ball_geometry_folds(Y, lambdas, Theta_bar, N_vecs):
    """Theorem-12 ball geometry for K folds x L lambdas at once.

    Per-fold quantities live on the FULL row index with held-out rows zeroed
    (zero rows contribute nothing to any inner product, so the masked algebra
    is exactly the per-fold algebra).  ``Y``/``Theta_bar``/``N_vecs``:
    (K, N); ``lambdas``: (K, L) — per-fold grids may differ (folds progress
    at different rates).  Returns (centers (K, L, N), radii (K, L))."""
    return jax.vmap(grid_ball_geometry)(Y, lambdas, Theta_bar, N_vecs)


def _grid_rules_folds(spec: GroupSpec, alpha, C, radii, col_norms_f,
                      group_specnorms_f, use_pallas: bool = False):
    """Theorems 15/16 for every (fold, lambda, group/feature) triple.

    ``C`` (K, L, p), ``radii`` (K, L), per-fold norms (K, p) / (K, G).
    The group statistics go through ``_grid_group_stats_folds`` so the f32
    path keeps the fused fold-stack kernel.  Adaptive weights take the same
    conservative 1-Lipschitz bound as ``_grid_rules``."""
    r_g = radii[:, :, None] * group_specnorms_f[:, None, :]
    if spec.feature_weights is None:
        c_norm, c_inf = _grid_group_stats_folds(spec, C, use_pallas)
        s = sup_shrink_norm(c_norm, c_inf, r_g)
        group_keep = s >= alpha * spec.weights[None, None, :]

        t = jnp.abs(C) + radii[:, :, None] * col_norms_f[:, None, :]
        feat_keep = (t > 1.0) & group_keep[:, :, spec.group_ids]
        return group_keep, feat_keep
    w = spec.feature_weights.astype(C.dtype)
    c_norm = jax.vmap(jax.vmap(
        lambda row: group_norms(spec, shrink(row, w))))(C)
    s = c_norm + r_g
    group_keep = s >= alpha * spec.weights[None, None, :]

    t = jnp.abs(C) + radii[:, :, None] * col_norms_f[:, None, :]
    feat_keep = (t > w[None, None, :]) & group_keep[:, :, spec.group_ids]
    return group_keep, feat_keep


def tlfre_screen_grid_folds(X, Y, spec: GroupSpec, alpha, lambdas, Theta_bar,
                            N_vecs, col_norms_f, group_specnorms_f,
                            safety: float = 0.0, mus=None,
                            use_pallas: bool = False):
    """Fold-batched TLFre grid screen: K folds x L lambdas in ONE GEMM.

    Stacks the K fold ball geometries into a single
    ``(K*L, N) x (N, p)`` product against the SHARED full design matrix —
    fold-k centers are zero on fold-k's validation rows, so the full-X
    product equals the fold's own ``centers @ X_train``.  ``col_norms_f`` /
    ``group_specnorms_f`` are per-fold (K, p) / (K, G) norms of the masked
    design.  ``mus`` (optional, (K, p)): per-fold train-row column means;
    fold k's centered design is ``M_k X - m_k mu_k^T``, so every center/X
    inner product needs only the rank-one correction
    ``C -= sum(center) * mu_k`` — the shared GEMM survives leakage-free
    per-fold centering untouched.  ``use_pallas`` routes the group-stat
    reductions through the fused fold-stack kernel (f32 only).  Returns
    (group_keep (K, L, G), feat_keep (K, L, p), radii (K, L))."""
    K, L = lambdas.shape
    N = Y.shape[1]
    centers, radii = grid_ball_geometry_folds(Y, lambdas, Theta_bar, N_vecs)
    radii = radii * (1.0 + safety)
    C = mm(centers.reshape(K * L, N), X).reshape(K, L, X.shape[1])
    if mus is not None:
        C = C - centers.sum(axis=2)[:, :, None] * mus[:, None, :]
    group_keep, feat_keep = _grid_rules_folds(spec, alpha, C, radii,
                                              col_norms_f, group_specnorms_f,
                                              use_pallas)
    return group_keep, feat_keep, radii


def gap_safe_screen_grid_folds(spec: GroupSpec, alpha, c_thetas, radii,
                               col_norms_f, group_specnorms_f,
                               use_pallas: bool = False):
    """Fold-batched Gap-Safe grid rules: per-fold fixed centers ``c_thetas``
    (K, p), per-(fold, lambda) radii (K, L).  No GEMM — the K centers are
    already reduced to K GEMVs by the caller.

    The group statistics depend on the center only, so they are evaluated
    ONCE per fold on the (K, 1, p) layout (fused kernel when ``use_pallas``)
    and broadcast across the grid — L-fold less reduction work than the
    naive per-(fold, lambda) evaluation."""
    K, L = radii.shape
    r_g = radii[:, :, None] * group_specnorms_f[:, None, :]   # (K, L, G)
    if spec.feature_weights is None:
        c_norm, c_inf = _grid_group_stats_folds(spec, c_thetas[:, None, :],
                                                use_pallas)   # (K, 1, G)
        s = sup_shrink_norm(c_norm, c_inf, r_g)
        l2_thresh = 1.0
    else:
        w = spec.feature_weights.astype(c_thetas.dtype)
        c_norm = jax.vmap(
            lambda ct: group_norms(spec, shrink(ct, w)))(c_thetas)
        s = c_norm[:, None, :] + r_g
        l2_thresh = w[None, None, :]
    group_keep = s >= alpha * spec.weights[None, None, :]
    t = (jnp.abs(c_thetas)[:, None, :]
         + radii[:, :, None] * col_norms_f[:, None, :])
    feat_keep = (t > l2_thresh) & group_keep[:, :, spec.group_ids]
    return group_keep, feat_keep


def gap_safe_screen_grid(spec: GroupSpec, alpha, c_theta, radii, col_norms,
                         group_specnorms, use_pallas: bool = False):
    """Gap-Safe grid rules for a FIXED feasible dual center theta.

    SGL dual feasibility does not depend on lambda, so one feasible theta
    (e.g. the exact dual at the previous solved point) certifies a ball at
    EVERY remaining lambda with radius sqrt(2*gap_l)/lam_l.  The center is
    shared, so the screening GEMM collapses to the single GEMV
    ``c_theta = X^T theta`` — only the radii vary across the grid.

    Returns (group_keep (L, G), feat_keep (L, p)).
    """
    C = jnp.broadcast_to(c_theta[None, :], (radii.shape[0], c_theta.shape[0]))
    return _grid_rules(spec, alpha, C, radii, col_norms, group_specnorms,
                       use_pallas)


# ---------------------------------------------------------------------------
# Feature-sharded grid screens.
#
# Column-sharded counterparts of the grid screens above: ``ops`` is a
# ``distributed.feature_shard.FeatureOps`` executor, ``Xs`` the stacked
# ``(S, N, p_shard)`` blocks, ``specs`` the stacked local GroupSpecs, and the
# per-shard norms carry a leading shard axis.  The ball geometry (an N-space
# computation) stays global; the GEMM + Theorem-15/16 rules run entirely
# feature-local per shard — no collective fires (the Layer-4 audit pins
# this).  Pad columns/groups are arithmetically inert (see
# ``distributed.feature_shard``), so the stacked keep masks gather back to
# exactly the single-device masks.
# ---------------------------------------------------------------------------

def tlfre_screen_grid_feat(ops, Xs, specs, y, alpha, lambdas, theta_bar,
                           n_vec, col_norms_s, group_specnorms_s,
                           safety: float = 0.0):
    """Sharded ``tlfre_screen_grid``: returns (group_keep (S, L, G_shard),
    feat_keep (S, L, p_shard), radii (L,))."""
    centers, radii = grid_ball_geometry(y, lambdas, theta_bar, n_vec)
    radii = radii * (1.0 + safety)

    def body(loc, centers, radii, alpha):
        Xb, spec_loc, cn, gs = loc
        C = mm(centers, Xb)
        return _grid_rules(spec_loc, alpha, C, radii, cn, gs, False)

    group_keep_s, feat_keep_s = ops.fmap(
        body, (Xs, specs, col_norms_s, group_specnorms_s),
        centers, radii, alpha)
    return group_keep_s, feat_keep_s, radii


def gap_safe_screen_grid_feat(ops, specs, alpha, c_theta_s, radii,
                              col_norms_s, group_specnorms_s):
    """Sharded ``gap_safe_screen_grid``: the fixed center arrives already
    stacked (``c_theta_s`` (S, p_shard), e.g. the certified duals the
    sharded sweep emits).  Returns (group_keep (S, L, G_shard),
    feat_keep (S, L, p_shard))."""
    def body(loc, radii, alpha):
        spec_loc, ct, cn, gs = loc
        return gap_safe_screen_grid(spec_loc, alpha, ct, radii, cn, gs,
                                    False)

    return ops.fmap(body, (specs, c_theta_s, col_norms_s,
                           group_specnorms_s), radii, alpha)


def tlfre_screen_grid_folds_feat(ops, Xs, specs, Y, alpha, lambdas,
                                 Theta_bar, N_vecs, col_norms_sf,
                                 group_specnorms_sf, safety: float = 0.0,
                                 mus_s=None):
    """Sharded ``tlfre_screen_grid_folds``: per-fold norms are stacked
    (S, K, p_shard)/(S, K, G_shard), ``mus_s`` the stacked per-fold column
    means for centered CV.  Returns (group_keep (S, K, L, G_shard),
    feat_keep (S, K, L, p_shard), radii (K, L))."""
    K, L = lambdas.shape
    N = Y.shape[1]
    centers, radii = grid_ball_geometry_folds(Y, lambdas, Theta_bar, N_vecs)
    radii = radii * (1.0 + safety)
    csum = centers.sum(axis=2)                                    # (K, L)

    if mus_s is None:
        def body(loc, centers, radii, alpha):
            Xb, spec_loc, cn, gs = loc
            C = mm(centers.reshape(K * L, N), Xb).reshape(
                K, L, Xb.shape[1])
            return _grid_rules_folds(spec_loc, alpha, C, radii, cn, gs,
                                     False)

        gk_s, fk_s = ops.fmap(
            body, (Xs, specs, col_norms_sf, group_specnorms_sf),
            centers, radii, alpha)
    else:
        def body(loc, centers, csum, radii, alpha):
            Xb, spec_loc, cn, gs, mu = loc
            C = mm(centers.reshape(K * L, N), Xb).reshape(
                K, L, Xb.shape[1])
            C = C - csum[:, :, None] * mu[:, None, :]
            return _grid_rules_folds(spec_loc, alpha, C, radii, cn, gs,
                                     False)

        gk_s, fk_s = ops.fmap(
            body, (Xs, specs, col_norms_sf, group_specnorms_sf, mus_s),
            centers, csum, radii, alpha)
    return gk_s, fk_s, radii


def gap_safe_screen_grid_folds_feat(ops, specs, alpha, c_thetas_s, radii,
                                    col_norms_sf, group_specnorms_sf):
    """Sharded ``gap_safe_screen_grid_folds``: stacked per-fold centers
    ``c_thetas_s`` (S, K, p_shard).  Returns (group_keep
    (S, K, L, G_shard), feat_keep (S, K, L, p_shard))."""
    def body(loc, radii, alpha):
        spec_loc, ct, cn, gs = loc
        return gap_safe_screen_grid_folds(spec_loc, alpha, ct, radii, cn,
                                          gs, False)

    return ops.fmap(body, (specs, c_thetas_s, col_norms_sf,
                           group_specnorms_sf), radii, alpha)


def gap_safe_grid_radii(y, lambdas, theta, resid, penalty):
    """sqrt(2 * gap_l) / lam_l per grid point, for primal iterate beta with
    residual ``resid = y - X beta`` and penalty ``Omega(beta)`` (so
    P_l = 0.5||resid||^2 + lam_l * Omega) and feasible dual theta.

    Squared loss only — the squared-loss engine keeps this literal graph;
    other losses go through ``gap_safe_grid_radii_loss``."""
    lambdas = jnp.asarray(lambdas)
    p_half = 0.5 * jnp.vdot(resid, resid)
    d = y[None, :] - lambdas[:, None] * theta[None, :]
    dual = 0.5 * jnp.vdot(y, y) - 0.5 * jnp.sum(d * d, axis=1)
    gap = jnp.maximum(p_half + lambdas * penalty - dual, 0.0)
    return jnp.sqrt(2.0 * gap) / lambdas


def gap_safe_grid_radii_loss(loss, y, lambdas, theta, fit, resid, penalty):
    """Loss-generic Gap-Safe grid radii: ``sqrt(2 * gamma * gap_l) / lam_l``
    per grid point (the dual is ``lam^2/gamma``-strongly concave for a loss
    with smoothness constant ``gamma``).

    ``fit = X beta`` and ``resid = loss.residual(y, fit)`` for the primal
    iterate; ``theta`` must be dual-feasible (feasibility does not depend on
    lambda, so one certified dual serves the whole grid).
    """
    lambdas = jnp.asarray(lambdas)
    p_smooth = loss.primal_value(y, fit, resid)
    dual = jax.vmap(lambda lam: loss.dual_value(y, theta, lam))(lambdas)
    gap = jnp.maximum(p_smooth + lambdas * penalty - dual, 0.0)
    if loss.gamma != 1.0:
        gap = loss.gamma * gap
    return jnp.sqrt(2.0 * gap) / lambdas
