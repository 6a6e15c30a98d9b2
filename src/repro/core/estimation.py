"""Dual-optimum estimation via normal cones (paper Theorem 12 / Theorem 21).

Given the exact dual optimum ``theta_bar`` at a previous path point
``lam_bar <= lam_max`` and a normal-cone direction ``n`` at it, the dual
optimum at lam < lam_bar lies in the ball

    || theta*(lam) - (theta_bar + v_perp/2) || <= ||v_perp|| / 2

with v = y/lam - theta_bar and v_perp its component orthogonal to n.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .fenchel import shrink
from .groups import GroupSpec, broadcast_to_features
from .linalg import mm


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DualBall:
    """Ball certified to contain the dual optimum."""
    center: jnp.ndarray   # (N,)
    radius: jnp.ndarray   # scalar

    def tree_flatten(self):
        return (self.center, self.radius), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def project_out_normal(v, n_vec):
    """``v_perp``: the component of ``v`` orthogonal to ``n_vec``.

    Shared zero-normal guard for Theorem 12(ii) and its grid form
    (``screening.grid_ball_geometry``): when ``n_vec == 0`` (or its squared
    norm underflows) the normal-cone constraint is vacuous and ``v_perp = v``
    exactly — no NaN and no division by a clamped denominator, in float32 as
    well as float64.  At ``lam == lam_bar`` we have ``v == 0`` and hence a
    ball of radius exactly 0.  ``v`` may be (N,) or batched (..., N) against
    a single (N,) normal.
    """
    n2 = jnp.vdot(n_vec, n_vec)
    coef = jnp.where(n2 > 0, jnp.tensordot(v, n_vec, axes=(-1, 0))
                     / jnp.where(n2 > 0, n2, 1.0), 0.0)
    return v - coef[..., None] * n_vec if v.ndim > 1 else v - coef * n_vec


def normal_vector_sgl(X, y, spec: GroupSpec, lam_bar, lam_max, theta_bar,
                      g_star) -> jnp.ndarray:
    """n_alpha(lam_bar) of Theorem 12.

    * lam_bar <  lam_max:  y/lam_bar - theta_bar     (Prop. 11(iii))
    * lam_bar == lam_max:  X_* S_1(X_*^T y/lam_max)  (the active-group normal)
    """
    at_max = jnp.asarray(lam_bar >= lam_max * (1.0 - 1e-12))
    n_interior = y / lam_bar - theta_bar
    w = shrink(mm(X.T, y / lam_max))
    w_star = jnp.where(broadcast_to_features(spec, jnp.arange(spec.num_groups)
                                             ) == g_star, w, 0.0)
    n_boundary = mm(X, w_star)
    return jnp.where(at_max, n_boundary, n_interior)


def estimate_dual_ball(y, lam, lam_bar, theta_bar, n_vec) -> DualBall:
    """Theorem 12(ii) (identical algebra for Theorem 21)."""
    v = y / lam - theta_bar
    v_perp = project_out_normal(v, n_vec)
    return DualBall(center=theta_bar + 0.5 * v_perp,
                    radius=0.5 * jnp.linalg.norm(v_perp))


def gap_safe_ball(theta_feasible, primal_value, dual_value, lam,
                  gamma: float = 1.0) -> DualBall:
    """Beyond-paper: Gap-Safe ball (Fercoq et al., 2015) reusing the same
    Theorem-15 sup machinery.  For a loss with smoothness constant ``gamma``
    (gradient ``gamma``-Lipschitz per sample; 1 for squared, 1/4 for
    logistic) the dual is ``lam^2/gamma``-strongly concave, so

        ||theta* - theta|| <= sqrt(2 * gamma * gap) / lam .

    The scaling is gated on ``gamma != 1.0`` so squared-loss graphs are
    unchanged.
    """
    gap = jnp.maximum(primal_value - dual_value, 0.0)
    if gamma != 1.0:
        gap = gamma * gap
    return DualBall(center=theta_feasible, radius=jnp.sqrt(2.0 * gap) / lam)
