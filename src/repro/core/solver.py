"""FISTA solvers for SGL (3) and nonnegative Lasso (80).

Pure-JAX accelerated proximal gradient with duality-gap stopping, the
counterpart of the SLEP solver used by the paper.  The dual point used in the
gap is the residual scaled onto the feasible set with the SAME
piecewise-quadratic root machinery as Lemma 9 (see lambda_max.dual_scaling_sgl)
— this makes the reported gaps true optimality certificates.

Everything is a ``lax.while_loop`` so path drivers can jit one step shape and
reuse it across the whole lambda grid.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .fenchel import sgl_penalty
from .groups import GroupSpec
from .lambda_max import dual_scaling_sgl
from .linalg import mm
from .losses import SQUARED, Loss
from .prox import nn_lasso_prox, sgl_prox
from . import dpc as _dpc


class SolveResult(NamedTuple):
    beta: jnp.ndarray
    theta: jnp.ndarray          # feasible dual point (y - X beta)/lam, scaled
    gap: jnp.ndarray
    iters: jnp.ndarray


def _restart(z, beta_new, beta):
    """O'Donoghue-Candes adaptive restart: reset the momentum when the
    extrapolated direction opposes progress, ``<z - b', b' - b> > 0``.

    Both differences carry about ``eps * |b|`` of rounding each, so near
    convergence a bare ``> 0`` test is a coin flip.  In float32 it then kills
    the momentum every other step and FISTA stalls at ISTA's rate short of a
    1e-5 certificate (image-dictionary nonnegative Lasso: rows capped at
    ``max_iter`` that converge in ~2k iterations once the test ignores
    noise).  The test asks for more than 64 ulps of that rounding (the
    certificate floor's margin, ``Loss.effective_tol``); in float64 the
    margin sits far below any step the solver takes."""
    d, step = z - beta_new, beta_new - beta
    noise = (64.0 * jnp.finfo(beta.dtype).eps * jnp.linalg.norm(beta_new)
             * (jnp.linalg.norm(d) + jnp.linalg.norm(step)))
    return jnp.vdot(d, step) > noise


# ---------------------------------------------------------------------------
# SGL
# ---------------------------------------------------------------------------

def _sgl_gap(X, y, spec, lam, alpha, beta, loss: Loss = SQUARED):
    """(primal, dual, theta_feasible) at beta."""
    fit = mm(X, beta)
    resid = loss.residual(y, fit)
    rho = resid / lam
    s = dual_scaling_sgl(spec, mm(X.T, rho), alpha)
    theta = s * rho
    p = loss.primal_value(y, fit, resid) + lam * sgl_penalty(spec, beta, alpha)
    d = loss.dual_value(y, theta, lam)
    return p, d, theta


def fista_sgl(X, y, spec: GroupSpec, lam, alpha, lipschitz, beta0, *,
              max_iter: int = 20000, check_every: int = 10, tol: float = 1e-9,
              prox=None, loss: Loss = SQUARED) -> SolveResult:
    """Un-jitted FISTA core for problem (3); traceable inside scans.

    ``lam`` may be a traced scalar, so the batched path engine can sweep a
    whole lambda chunk inside one ``lax.scan`` without retracing.  ``prox``
    optionally overrides the (z, t_l1, t_group) -> z' proximal step — the
    engine injects the fused Pallas kernel here.  ``loss`` swaps the smooth
    data-fit term; ``lipschitz`` stays the design bound ``||X||^2`` — the
    loss's smoothness factor is applied here (gated so squared-loss traces
    are unchanged).
    """
    dtype = X.dtype
    beta0 = beta0.astype(dtype)
    if loss.gamma != 1.0:
        lipschitz = lipschitz * loss.gamma
    tol = loss.effective_tol(tol, dtype)
    t_step = 1.0 / lipschitz
    if spec.feature_weights is None:
        t_l1 = t_step * lam                   # lam2 = lam
    else:
        # adaptive l1: per-feature thresholds; shrink() broadcasts
        t_l1 = t_step * lam * spec.feature_weights.astype(dtype)
    # spec.weights is float64 master data; cast once at the boundary so the
    # scan body stays dtype-pure (no silent f64 promotion on f32 problems)
    t_group = t_step * lam * alpha * spec.weights.astype(dtype)
    gap_scale = loss.gap_scale(y)
    if prox is None:
        prox = lambda v, a, b: sgl_prox(spec, v, a, b)

    def prox_grad(z):
        g = mm(X.T, loss.grad(y, mm(X, z)))
        # spec.weights is float64 for exactness; pin the iterate dtype so
        # float32 problems under jax_enable_x64 keep a stable carry
        return prox(z - t_step * g, t_l1, t_group).astype(dtype)

    def inner(carry, _):
        beta, z, tk = carry
        beta_new = prox_grad(z)
        tk = jnp.where(_restart(z, beta_new, beta), 1.0, tk)
        tk1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        z_new = beta_new + ((tk - 1.0) / tk1) * (beta_new - beta)
        return (beta_new, z_new, tk1), None

    def cond(state):
        (beta, z, tk), it, gap = state
        return (gap > tol * gap_scale) & (it < max_iter)

    def body(state):
        carry, it, _ = state
        carry, _ = jax.lax.scan(inner, carry, None, length=check_every)
        pval, dval, _ = _sgl_gap(X, y, spec, lam, alpha, carry[0], loss)
        return carry, it + check_every, (pval - dval).astype(dtype)

    init = ((beta0, beta0, jnp.asarray(1.0, dtype)), jnp.asarray(0), jnp.asarray(jnp.inf, dtype))
    (beta, _, _), iters, gap = jax.lax.while_loop(cond, body, init)
    _, _, theta = _sgl_gap(X, y, spec, lam, alpha, beta, loss)
    return SolveResult(beta, theta, gap, iters)


@functools.partial(jax.jit,
                   static_argnames=("max_iter", "check_every", "loss"))
def solve_sgl(X, y, spec: GroupSpec, lam, alpha, lipschitz, beta0=None, *,
              max_iter: int = 20000, check_every: int = 10,
              tol: float = 1e-9, loss: Loss = SQUARED) -> SolveResult:
    """FISTA for problem (3).  ``tol`` is a relative duality-gap tolerance
    (gap <= tol * loss.gap_scale(y); 0.5||y||^2 for squared loss).
    ``lipschitz`` is the design bound ``||X||^2`` for every loss."""
    p = X.shape[1]
    beta0 = jnp.zeros(p, X.dtype) if beta0 is None else beta0
    return fista_sgl(X, y, spec, lam, alpha, lipschitz, beta0,
                     max_iter=max_iter, check_every=check_every, tol=tol,
                     loss=loss)


# ---------------------------------------------------------------------------
# Nonnegative Lasso
# ---------------------------------------------------------------------------

def _nn_gap(X, y, lam, beta):
    rho = (y - mm(X, beta)) / lam
    s = _dpc.dual_scaling_nn(mm(X.T, rho))
    theta = s * rho
    p = _dpc.nn_primal_objective(X, y, beta, lam)
    d = _dpc.nn_dual_objective(y, theta, lam)
    return p, d, theta


def fista_nn_lasso(X, y, lam, lipschitz, beta0, *, max_iter: int = 20000,
                   check_every: int = 10, tol: float = 1e-9) -> SolveResult:
    """Un-jitted FISTA core for problem (80); traceable inside scans."""
    dtype = X.dtype
    beta0 = beta0.astype(dtype)
    tol = SQUARED.effective_tol(tol, dtype)
    t_step = 1.0 / lipschitz
    gap_scale = SQUARED.gap_scale(y)

    def inner(carry, _):
        beta, z, tk = carry
        g = mm(X.T, mm(X, z) - y)
        beta_new = nn_lasso_prox(z - t_step * g, t_step * lam)
        tk = jnp.where(_restart(z, beta_new, beta), 1.0, tk)
        tk1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * tk * tk))
        z_new = beta_new + ((tk - 1.0) / tk1) * (beta_new - beta)
        return (beta_new, z_new, tk1), None

    def cond(state):
        _, it, gap = state
        return (gap > tol * gap_scale) & (it < max_iter)

    def body(state):
        carry, it, _ = state
        carry, _ = jax.lax.scan(inner, carry, None, length=check_every)
        pval, dval, _ = _nn_gap(X, y, lam, carry[0])
        return carry, it + check_every, (pval - dval).astype(dtype)

    init = ((beta0, beta0, jnp.asarray(1.0, dtype)), jnp.asarray(0), jnp.asarray(jnp.inf, dtype))
    (beta, _, _), iters, gap = jax.lax.while_loop(cond, body, init)
    _, _, theta = _nn_gap(X, y, lam, beta)
    return SolveResult(beta, theta, gap, iters)


@functools.partial(jax.jit, static_argnames=("max_iter", "check_every"))
def solve_nn_lasso(X, y, lam, lipschitz, beta0=None, *, max_iter: int = 20000,
                   check_every: int = 10, tol: float = 1e-9) -> SolveResult:
    """FISTA for problem (80) with prox (v - t*lam)_+."""
    p = X.shape[1]
    beta0 = jnp.zeros(p, X.dtype) if beta0 is None else beta0
    return fista_nn_lasso(X, y, lam, lipschitz, beta0, max_iter=max_iter,
                          check_every=check_every, tol=tol)
