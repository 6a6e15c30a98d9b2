"""Batched path engine: parity with the legacy drivers, host-sync /
compilation accounting, grid-rule safety, and Pallas wiring.

The parity bound is the acceptance criterion of the engine: under float64
at tight solver tolerance the batched engine must reproduce the legacy
per-lambda driver to 1e-8 across every screening mode, while making fewer
host round-trips and O(log p) solver compilations.
"""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (GroupSpec, column_norms, group_spectral_norms,
                        lambda_max_sgl, lambda_max_nn, nn_lasso_path,
                        normal_vector_sgl, normal_vector_nn, Plan, Problem,
                        SGLSession, sgl_path,
                        solve_sgl, solve_nn_lasso, spectral_norm,
                        default_lambda_grid)
from repro.core.dpc import dpc_screen_grid
from repro.core.screening import tlfre_screen_grid


def _sgl_problem(seed=7, N=60, G=40, n=6):
    rng = np.random.default_rng(seed)
    p = G * n
    X = rng.standard_normal((N, p))
    beta = np.zeros(p)
    for g in rng.choice(G, 5, replace=False):
        beta[g * n + rng.choice(n, 3, replace=False)] = rng.standard_normal(3)
    y = X @ beta + 0.01 * rng.standard_normal(N)
    return X, y, GroupSpec.uniform_groups(G, n)


def _nn_problem(seed=3, N=50, p=240):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((N, p))
    beta = np.zeros(p)
    beta[rng.choice(p, 15, replace=False)] = np.abs(rng.standard_normal(15))
    y = X @ beta + 0.01 * rng.standard_normal(N)
    return X, y


# ---------------------------------------------------------------------------
# Parity + host-sync accounting (the engine acceptance criteria)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("screen", ["tlfre", "gapsafe", "none"])
def test_sgl_engine_parity(screen):
    X, y, spec = _sgl_problem()
    p = spec.num_features
    J = 16
    kw = dict(n_lambdas=J, tol=1e-13, max_iter=200_000, screen=screen)
    res_b = sgl_path(X, y, spec, 1.0, engine="batched", min_bucket=32, **kw)
    res_l = sgl_path(X, y, spec, 1.0, **kw)
    np.testing.assert_allclose(res_b.betas, res_l.betas, atol=1e-8)

    stats = res_b.stats
    assert stats is not None
    # fewer host round-trips than the legacy one-per-lambda protocol
    assert stats.n_segments < J
    # O(log p) solver compilations: distinct sweep shape keys only
    assert stats.n_compilations <= (
        math.ceil(math.log2(p)) + math.ceil(math.log2(J)) + 4)
    if screen != "none":
        # screening must actually reduce the early-path solver size
        assert res_b.kept_features[1] < p


@pytest.mark.parametrize("screen", ["dpc", "gapsafe", "none"])
def test_nn_engine_parity(screen):
    X, y = _nn_problem()
    p = X.shape[1]
    J = 16
    legacy_screen = "dpc" if screen == "gapsafe" else screen
    res_b = nn_lasso_path(X, y, n_lambdas=J, tol=1e-13, max_iter=200_000,
                          screen=screen, engine="batched", min_bucket=32)
    res_l = nn_lasso_path(X, y, n_lambdas=J, tol=1e-13, max_iter=200_000,
                          screen=legacy_screen)
    np.testing.assert_allclose(res_b.betas, res_l.betas, atol=1e-8)
    stats = res_b.stats
    assert stats.n_segments < J
    assert stats.n_compilations <= (
        math.ceil(math.log2(p)) + math.ceil(math.log2(J)) + 4)


@pytest.mark.parametrize("kind", ["sgl", "nn", "sgl-cv"])
def test_capped_rows_are_counted_uncertified(kind):
    """A row whose solve stops at ``max_iter`` above ``tol`` is kept, so
    the path goes on, and counted in ``n_uncertified``; a path whose rows
    all converge counts none."""
    if kind == "sgl-cv":
        X, y, spec = _sgl_problem()
        sess = SGLSession(Problem.sgl(X, y, spec))
        run = lambda **kw: sess.cv(Plan(n_lambdas=8, n_folds=3, **kw))
    elif kind == "sgl":
        X, y, spec = _sgl_problem()
        run = lambda **kw: sgl_path(X, y, spec, 1.0, n_lambdas=8,
                                    engine="batched", min_bucket=32, **kw)
    else:
        X, y = _nn_problem()
        run = lambda **kw: nn_lasso_path(X, y, n_lambdas=8, engine="batched",
                                         min_bucket=32, **kw)
    capped = run(tol=1e-13, max_iter=20)
    assert capped.stats.n_uncertified > 0
    betas = capped.fold_betas if kind == "sgl-cv" else capped.betas
    assert np.all(np.isfinite(betas))
    assert run(tol=1e-8, max_iter=200_000).stats.n_uncertified == 0


def test_engine_accepts_custom_lambda_grid():
    X, y, spec = _sgl_problem(seed=11, G=20, n=5)
    lam_max = float(lambda_max_sgl(spec, jnp.asarray(X).T @ jnp.asarray(y),
                                   1.0)[0])
    lambdas = lam_max * np.asarray([1.0, 0.7, 0.4, 0.2, 0.1])
    res_b = sgl_path(X, y, spec, 1.0, lambdas=lambdas, tol=1e-13,
                     engine="batched", min_bucket=32)
    res_l = sgl_path(X, y, spec, 1.0, lambdas=lambdas, tol=1e-13)
    np.testing.assert_allclose(res_b.betas, res_l.betas, atol=1e-8)
    assert np.all(res_b.betas[0] == 0.0)        # lam_max endpoint


def test_legacy_engine_rejects_engine_kwargs():
    X, y, spec = _sgl_problem(seed=1, G=8, n=4)
    with pytest.raises(TypeError):
        sgl_path(X, y, spec, 1.0, n_lambdas=4, min_bucket=32)
    with pytest.raises(ValueError):
        sgl_path(X, y, spec, 1.0, n_lambdas=4, engine="warp")


# ---------------------------------------------------------------------------
# Grid-rule safety: nothing active is ever discarded
# ---------------------------------------------------------------------------

def test_tlfre_grid_rules_never_discard_active():
    """Every feature with |beta*| > 0 at any grid lambda must survive the
    one-shot whole-grid screen for that lambda."""
    X, y, spec = _sgl_problem(seed=5, N=50, G=25, n=4)
    X, y = jnp.asarray(X), jnp.asarray(y)
    alpha = 1.0
    lam_max, g_star = lambda_max_sgl(spec, X.T @ y, alpha)
    lam_max = float(lam_max)
    col_n = column_norms(X)
    gspec = group_spectral_norms(X, spec)
    L = spectral_norm(X) ** 2
    lambdas = default_lambda_grid(lam_max, 8)[1:]
    theta_bar = y / lam_max
    n_vec = normal_vector_sgl(X, y, spec, lam_max, lam_max, theta_bar, g_star)
    gk, fk, _ = tlfre_screen_grid(X, y, spec, alpha,
                                  jnp.asarray(lambdas), lam_max, theta_bar,
                                  n_vec, col_n, gspec)
    gk, fk = np.asarray(gk), np.asarray(fk)
    gid = np.asarray(spec.group_ids)
    for i, lam in enumerate(lambdas):
        sol = solve_sgl(X, y, spec, float(lam), alpha, L, tol=1e-13,
                        max_iter=200_000)
        active = np.abs(np.asarray(sol.beta)) > 1e-9
        assert not np.any(active & ~gk[i][gid]), f"L1 dropped active @ {i}"
        assert not np.any(active & ~fk[i]), f"L2 dropped active @ {i}"


def test_dpc_grid_rules_never_discard_active():
    X, y = _nn_problem(seed=9, N=40, p=160)
    X, y = jnp.asarray(X), jnp.asarray(y)
    lam_max, i_star = lambda_max_nn(X.T @ y)
    lam_max = float(lam_max)
    L = spectral_norm(X) ** 2
    lambdas = default_lambda_grid(lam_max, 8)[1:]
    theta_bar = y / lam_max
    n_vec = normal_vector_nn(X, y, lam_max, lam_max, theta_bar, i_star)
    fk, _ = dpc_screen_grid(X, y, jnp.asarray(lambdas), theta_bar, n_vec,
                            column_norms(X))
    fk = np.asarray(fk)
    for i, lam in enumerate(lambdas):
        sol = solve_nn_lasso(X, y, float(lam), L, tol=1e-13, max_iter=200_000)
        active = np.asarray(sol.beta) > 1e-9
        assert not np.any(active & ~fk[i]), f"DPC dropped active @ {i}"


# ---------------------------------------------------------------------------
# Pallas wiring (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.pallas
def test_engine_pallas_path_matches_jnp_path():
    """use_pallas=True routes screening stats + prox + the certification
    GEMV through the kernels (interpret mode here); float32 tolerance."""
    X, y, spec = _sgl_problem(seed=2, N=40, G=24, n=5)
    X32 = np.asarray(X, np.float32)
    y32 = np.asarray(y, np.float32)
    kw = dict(n_lambdas=8, tol=1e-6, safety=1e-4, max_iter=4000,
              check_every=20, engine="batched", min_bucket=32)
    res_p = sgl_path(X32, y32, spec, 1.0, use_pallas=True, **kw)
    res_j = sgl_path(X32, y32, spec, 1.0, use_pallas=False, **kw)
    np.testing.assert_allclose(res_p.betas, res_j.betas, atol=5e-4)


@pytest.mark.pallas
def test_engine_pallas_ignored_for_float64():
    """float64 exactness runs must never engage the float32 kernels."""
    from repro.core.path_engine import _pallas_active
    assert not _pallas_active(True, jnp.float64)
    assert not _pallas_active(None, jnp.float64)
    assert _pallas_active(True, jnp.float32)
