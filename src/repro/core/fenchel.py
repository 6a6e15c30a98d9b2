"""Fenchel-dual machinery for SGL (paper Section 3).

The central objects are the shrinkage operator ``S_gamma`` (Eq. 1/19) and the
closed-form decomposition of any point of the summed dual set
``D_g = alpha*sqrt(n_g)*B2 + B_inf`` (Lemma 3 / Remark 2):

    xi = P_Binf(xi) + S_1(xi),    P_Binf(xi) in B_inf,  S_1(xi) in C_g

which turns the (a-priori nontrivial) feasibility test of the Lagrangian dual
(4) into the explicit test ``||S_1(X_g^T theta)|| <= alpha*sqrt(n_g)`` of the
Fenchel dual (13).
"""
from __future__ import annotations

import jax.numpy as jnp

from .groups import GroupSpec, group_norms, group_max_abs
from .linalg import mm


def shrink(w: jnp.ndarray, gamma=1.0) -> jnp.ndarray:
    """Soft-threshold / shrinkage operator S_gamma (Eq. 1)."""
    return jnp.sign(w) * jnp.maximum(jnp.abs(w) - gamma, 0.0)


def proj_binf(w: jnp.ndarray, gamma=1.0) -> jnp.ndarray:
    """Projection onto the l_inf ball of radius gamma."""
    return jnp.clip(w, -gamma, gamma)


def dual_decompose(xi: jnp.ndarray, gamma=1.0):
    """Decompose xi in gamma*B_inf + C  as (P_Binf, S_gamma) (Remark 2).

    The identity ``xi == proj + shr`` holds for EVERY xi (Eq. 19); membership
    of the shrunk part in C_g is what feasibility checks.
    """
    return proj_binf(xi, gamma), shrink(xi, gamma)


def sgl_feasibility_margin(spec: GroupSpec, xt_theta: jnp.ndarray,
                           alpha: jnp.ndarray) -> jnp.ndarray:
    """Per-group feasibility margin of the Fenchel dual (13).

    Returns ``||S_w(X_g^T theta)|| - alpha*w_g``; theta is dual-feasible iff
    every entry is <= 0.  The shrinkage threshold is the adaptive per-feature
    weight when the spec carries one (``S_1`` otherwise — the paper's case).
    """
    gamma = (1.0 if spec.feature_weights is None
             else spec.feature_weights.astype(xt_theta.dtype))
    return (group_norms(spec, shrink(xt_theta, gamma))
            - alpha * spec.weights.astype(xt_theta.dtype))


def sgl_dual_feasible(spec: GroupSpec, xt_theta: jnp.ndarray, alpha,
                      tol: float = 0.0) -> jnp.ndarray:
    return jnp.all(sgl_feasibility_margin(spec, xt_theta, alpha) <= tol)


def sgl_dual_objective(y: jnp.ndarray, theta: jnp.ndarray, lam) -> jnp.ndarray:
    """Dual objective sup-form of (4): 0.5||y||^2 - 0.5*lam^2*||y/lam - theta||^2."""
    d = y - lam * theta
    return 0.5 * jnp.vdot(y, y) - 0.5 * jnp.vdot(d, d)


def weighted_l1(spec: GroupSpec, beta) -> jnp.ndarray:
    """l1 part of the SGL penalty: ``sum w_f |beta_f]`` when the spec carries
    adaptive feature weights, the classical ``sum |beta_f|`` otherwise (the
    unweighted expression is kept literal so squared-loss graphs are
    unchanged)."""
    if spec.feature_weights is None:
        return jnp.sum(jnp.abs(beta))
    return jnp.sum(spec.feature_weights.astype(beta.dtype) * jnp.abs(beta))


def sgl_penalty(spec: GroupSpec, beta, alpha) -> jnp.ndarray:
    """SGL penalty ``alpha * sum_g W_g ||beta_g|| + sum_f w_f |beta_f|``
    (adaptive weights included; loss-independent)."""
    return (alpha * jnp.sum(spec.weights.astype(beta.dtype)
                            * group_norms(spec, beta))
            + weighted_l1(spec, beta))


def sgl_primal_objective(X, y, beta, spec: GroupSpec, lam, alpha):
    """Objective of problem (3)."""
    r = y - mm(X, beta)
    pen = sgl_penalty(spec, beta, alpha)
    return 0.5 * jnp.vdot(r, r) + lam * pen


def group_inf_norms(spec: GroupSpec, x: jnp.ndarray) -> jnp.ndarray:
    return group_max_abs(spec, x)
