"""Pathwise SGL / nonnegative-Lasso drivers with TLFre / DPC screening.

Mirrors the paper's experimental protocol (Section 6): a geometric grid of 100
lambda values from lambda_max down to 0.01*lambda_max; at each step the
screening rule runs against the previous EXACT dual optimum, the certified-
zero columns are *physically removed*, the reduced problem is solved
(warm-started), and the full solution is reassembled.

Two screening modes:
  * ``screen='tlfre'``   — the paper's sequential rule (Theorems 12/15/16/17).
  * ``screen='gapsafe'`` — beyond-paper dynamic Gap-Safe ball reusing the same
    Theorem-15 sup machinery (recorded separately in EXPERIMENTS.md §Perf).
  * ``screen='none'``    — baseline solver, for speedup measurements.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .dpc import (dpc_screen, dual_scaling_nn, lambda_max_nn, nn_dual_objective,
                  nn_primal_objective, normal_vector_nn)
from .estimation import DualBall, estimate_dual_ball, gap_safe_ball, normal_vector_sgl
from .fenchel import sgl_dual_objective, sgl_primal_objective
from .groups import GroupSpec
from .lambda_max import dual_scaling_sgl, lambda_max_sgl
from .linalg import column_norms, group_spectral_norms, mm, spectral_norm
from .screening import tlfre_screen
from .solver import solve_nn_lasso, solve_sgl


@dataclasses.dataclass
class PathResult:
    lambdas: np.ndarray                 # (J,)
    betas: np.ndarray                   # (J, p)
    lam_max: float
    screen_time: float                  # total screening seconds
    solve_time: float                   # total solver seconds
    setup_time: float                   # norms / lipschitz precompute
    iters: np.ndarray                   # (J,)
    kept_features: np.ndarray           # (J,) columns entering the solver
    kept_groups: Optional[np.ndarray] = None
    stats: Optional[object] = None      # EngineStats when engine="batched"

    @property
    def total_time(self):
        return self.screen_time + self.solve_time + self.setup_time


def default_lambda_grid(lam_max: float, n: int = 100,
                        min_ratio: float = 0.01) -> np.ndarray:
    """Paper protocol: n values equally spaced on log(lambda/lambda_max)
    from 1.0 down to min_ratio — INCLUDING the lam_max endpoint."""
    return lam_max * np.logspace(0.0, np.log10(min_ratio), n)


def _bucket(n: int, minimum: int = 64) -> int:
    """Next power-of-two bucket; keeps jitted solver shapes to O(log p)."""
    b = minimum
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# SGL path
# ---------------------------------------------------------------------------

def sgl_path(X, y, spec: GroupSpec, alpha, *, lambdas=None, n_lambdas=100,
             min_ratio=0.01, screen: str = "tlfre", tol=1e-9,
             max_iter: int = 20000, safety: float = 0.0,
             specnorm_method: str = "power", check_every: int = 10,
             engine: str = "legacy", **engine_kwargs) -> PathResult:
    """``engine='legacy'`` is the paper-protocol per-lambda driver below;
    ``engine='batched'`` is a thin shim over the declarative API — it
    builds a one-shot ``Problem``/``Plan`` and runs ``SGLSession.path``
    (same engine, same arguments, bit-identical results; a persistent
    session additionally reuses compiled buckets across calls).  The
    batched engine accepts the extra knobs ``use_pallas`` / ``min_bucket``
    / ``min_group_bucket`` / ``margin`` / ``chunk_init``."""
    if engine == "batched":
        from .problem import Plan, Problem, warn_legacy_entry_point
        from .session import SGLSession
        warn_legacy_entry_point("sgl_path(engine='batched')",
                                "SGLSession.path")
        plan = Plan(alpha=alpha, lambdas=lambdas, n_lambdas=n_lambdas,
                    min_ratio=min_ratio, screen=screen, tol=tol,
                    max_iter=max_iter, safety=safety,
                    specnorm_method=specnorm_method,
                    check_every=check_every, **engine_kwargs)
        return SGLSession(Problem.sgl(X, y, spec)).path(plan)
    if engine != "legacy":
        raise ValueError(f"unknown engine {engine!r}")
    if engine_kwargs:
        raise TypeError(f"engine='legacy' takes no extra kwargs, got "
                        f"{sorted(engine_kwargs)}")
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    N, p = X.shape

    t0 = time.perf_counter()
    xty = mm(X.T, y)
    lam_max, g_star = lambda_max_sgl(spec, xty, alpha)
    lam_max = float(lam_max)
    col_n = column_norms(X)
    if specnorm_method == "power":
        gspec = group_spectral_norms(X, spec)
    else:
        from .linalg import group_frobenius_norms
        gspec = group_frobenius_norms(X, spec)
    L = spectral_norm(X) ** 2
    jax.block_until_ready((col_n, gspec, L))
    setup_time = time.perf_counter() - t0

    if lambdas is None:
        lambdas = default_lambda_grid(lam_max, n_lambdas, min_ratio)
    lambdas = np.asarray(lambdas, dtype=float)
    J = len(lambdas)

    betas = np.zeros((J, p))
    iters = np.zeros(J, dtype=np.int64)
    kept_feat = np.zeros(J, dtype=np.int64)
    kept_grp = np.zeros(J, dtype=np.int64)
    screen_time = 0.0
    solve_time = 0.0

    X_np = np.asarray(X)
    theta_bar = jnp.asarray(y) / lam_max      # exact dual at lam_max (Thm 8)
    lam_bar = lam_max
    beta_prev = np.zeros(p)

    for j, lam in enumerate(lambdas):
        if lam >= lam_max * (1.0 - 1e-12):
            betas[j] = 0.0
            kept_feat[j] = 0
            kept_grp[j] = 0
            continue

        if screen == "none":
            ts = time.perf_counter()
            res = solve_sgl(X, y, spec, lam, alpha, L,
                            beta0=jnp.asarray(beta_prev),
                            max_iter=max_iter, tol=tol,
                            check_every=check_every)
            jax.block_until_ready(res.beta)
            solve_time += time.perf_counter() - ts
            beta_prev = np.asarray(res.beta)
            betas[j] = beta_prev
            iters[j] = int(res.iters)
            kept_feat[j] = p
            kept_grp[j] = spec.num_groups
            theta_bar = res.theta
            lam_bar = lam
            continue

        # ---- screening against the previous exact dual optimum ------------
        ts = time.perf_counter()
        n_vec = normal_vector_sgl(X, y, spec, lam_bar, lam_max, theta_bar,
                                  g_star)
        ball = estimate_dual_ball(y, lam, lam_bar, theta_bar, n_vec)
        sres = tlfre_screen(X, spec, alpha, ball, col_n, gspec, safety=safety)
        feat_keep = np.asarray(sres.feat_keep)
        jax.block_until_ready(sres.feat_keep)
        screen_time += time.perf_counter() - ts

        kept_feat[j] = int(feat_keep.sum())
        kept_grp[j] = int(np.asarray(sres.group_keep).sum())

        ts = time.perf_counter()
        if kept_feat[j] == 0:
            beta_full = np.zeros(p)
            theta_bar = jnp.asarray(y) / lam
            iters[j] = 0
        else:
            p_b = min(_bucket(kept_feat[j]), p)
            g_b = min(_bucket(kept_grp[j] + 1, minimum=16), spec.num_groups + 1)
            sub_spec, col_idx = spec.bucketed_subset(feat_keep, p_b, g_b)
            X_sub = np.zeros((N, p_b), dtype=X_np.dtype)
            X_sub[:, :len(col_idx)] = X_np[:, col_idx]
            X_sub = jnp.asarray(X_sub)
            L_sub = spectral_norm(X_sub, iters=25) ** 2
            beta0 = np.zeros(p_b, dtype=X_np.dtype)
            beta0[:len(col_idx)] = beta_prev[col_idx]
            res = solve_sgl(X_sub, y, sub_spec, lam, alpha, L_sub,
                            beta0=jnp.asarray(beta0),
                            max_iter=max_iter, tol=tol,
                            check_every=check_every)
            beta_full = np.zeros(p)
            beta_full[col_idx] = np.asarray(res.beta)[:len(col_idx)]
            iters[j] = int(res.iters)
            # exact dual: residual from the REDUCED matrix (screened coefs
            # are provably zero), feasibility scaling over the full X
            rho = (y - mm(X_sub, res.beta)) / lam
            s = dual_scaling_sgl(spec, mm(X.T, rho), alpha)
            theta_bar = s * rho
            jax.block_until_ready(theta_bar)
        solve_time += time.perf_counter() - ts
        betas[j] = beta_full
        beta_prev = beta_full
        lam_bar = lam

    return PathResult(lambdas=lambdas, betas=betas, lam_max=lam_max,
                      screen_time=screen_time, solve_time=solve_time,
                      setup_time=setup_time, iters=iters,
                      kept_features=kept_feat, kept_groups=kept_grp)


# ---------------------------------------------------------------------------
# Nonnegative-Lasso path with DPC
# ---------------------------------------------------------------------------

def nn_lasso_path(X, y, *, lambdas=None, n_lambdas=100, min_ratio=0.01,
                  screen: str = "dpc", tol=1e-9, max_iter: int = 20000,
                  safety: float = 0.0, check_every: int = 10,
                  engine: str = "legacy", **engine_kwargs) -> PathResult:
    if engine == "batched":
        from .problem import Plan, Problem, warn_legacy_entry_point
        from .session import SGLSession
        warn_legacy_entry_point("nn_lasso_path(engine='batched')",
                                "SGLSession.path")
        plan = Plan(lambdas=lambdas, n_lambdas=n_lambdas,
                    min_ratio=min_ratio, screen=screen, tol=tol,
                    max_iter=max_iter, safety=safety,
                    check_every=check_every, **engine_kwargs)
        return SGLSession(Problem.nn_lasso(X, y)).path(plan)
    if engine != "legacy":
        raise ValueError(f"unknown engine {engine!r}")
    if engine_kwargs:
        raise TypeError(f"engine='legacy' takes no extra kwargs, got "
                        f"{sorted(engine_kwargs)}")
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    N, p = X.shape

    t0 = time.perf_counter()
    xty = mm(X.T, y)
    lam_max, i_star = lambda_max_nn(xty)
    lam_max = float(lam_max)
    if lam_max <= 0:
        raise ValueError("max_i <x_i, y> <= 0: nonnegative Lasso solution is "
                         "identically zero for every lambda > 0")
    col_n = column_norms(X)
    L = spectral_norm(X) ** 2
    jax.block_until_ready((col_n, L))
    setup_time = time.perf_counter() - t0

    if lambdas is None:
        lambdas = default_lambda_grid(lam_max, n_lambdas, min_ratio)
    lambdas = np.asarray(lambdas, dtype=float)
    J = len(lambdas)

    betas = np.zeros((J, p))
    iters = np.zeros(J, dtype=np.int64)
    kept_feat = np.zeros(J, dtype=np.int64)
    screen_time = 0.0
    solve_time = 0.0

    X_np = np.asarray(X)
    theta_bar = jnp.asarray(y) / lam_max
    lam_bar = lam_max
    beta_prev = np.zeros(p)

    for j, lam in enumerate(lambdas):
        if lam >= lam_max * (1.0 - 1e-12):
            continue

        if screen == "none":
            ts = time.perf_counter()
            res = solve_nn_lasso(X, y, lam, L, beta0=jnp.asarray(beta_prev),
                                 max_iter=max_iter, tol=tol,
                            check_every=check_every)
            jax.block_until_ready(res.beta)
            solve_time += time.perf_counter() - ts
            beta_prev = np.asarray(res.beta)
            betas[j] = beta_prev
            iters[j] = int(res.iters)
            kept_feat[j] = p
            theta_bar = res.theta
            lam_bar = lam
            continue

        ts = time.perf_counter()
        n_vec = normal_vector_nn(X, y, lam_bar, lam_max, theta_bar, i_star)
        ball = estimate_dual_ball(y, lam, lam_bar, theta_bar, n_vec)
        feat_keep = np.asarray(dpc_screen(X, ball, col_n, safety=safety))
        screen_time += time.perf_counter() - ts
        kept_feat[j] = int(feat_keep.sum())

        ts = time.perf_counter()
        if kept_feat[j] == 0:
            beta_full = np.zeros(p)
            theta_bar = jnp.asarray(y) / lam
            iters[j] = 0
        else:
            col_idx = np.nonzero(feat_keep)[0]
            p_b = min(_bucket(len(col_idx)), p)
            X_sub = np.zeros((N, p_b), dtype=X_np.dtype)
            X_sub[:, :len(col_idx)] = X_np[:, col_idx]
            X_sub = jnp.asarray(X_sub)
            L_sub = spectral_norm(X_sub, iters=25) ** 2
            beta0 = np.zeros(p_b, dtype=X_np.dtype)
            beta0[:len(col_idx)] = beta_prev[col_idx]
            res = solve_nn_lasso(X_sub, y, lam, L_sub,
                                 beta0=jnp.asarray(beta0),
                                 max_iter=max_iter, tol=tol,
                                 check_every=check_every)
            beta_full = np.zeros(p)
            beta_full[col_idx] = np.asarray(res.beta)[:len(col_idx)]
            iters[j] = int(res.iters)
            rho = (y - mm(X_sub, res.beta)) / lam
            s = dual_scaling_nn(mm(X.T, rho))
            theta_bar = s * rho
            jax.block_until_ready(theta_bar)
        solve_time += time.perf_counter() - ts
        betas[j] = beta_full
        beta_prev = beta_full
        lam_bar = lam

    return PathResult(lambdas=lambdas, betas=betas, lam_max=lam_max,
                      screen_time=screen_time, solve_time=solve_time,
                      setup_time=setup_time, iters=iters,
                      kept_features=kept_feat)


# ---------------------------------------------------------------------------
# Rejection-ratio bookkeeping (paper Section 6 metrics)
# ---------------------------------------------------------------------------

def rejection_ratios_sgl(spec: GroupSpec, beta_exact: np.ndarray,
                         group_keep: np.ndarray, feat_keep: np.ndarray,
                         zero_tol: float = 1e-10):
    """r1, r2 of Section 6.1: fractions of the m inactive features removed by
    layer 1 (whole groups) and layer 2 (extra features), respectively."""
    gid = np.asarray(spec.group_ids)
    inactive = np.abs(beta_exact) <= zero_tol
    m = max(int(inactive.sum()), 1)
    dropped_by_l1 = ~np.asarray(group_keep)[gid]
    r1 = float((dropped_by_l1 & inactive).sum()) / m
    dropped_by_l2 = (~np.asarray(feat_keep)) & (~dropped_by_l1)
    r2 = float((dropped_by_l2 & inactive).sum()) / m
    return r1, r2
