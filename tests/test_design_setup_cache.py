"""Engine set-up computes per call only what depends on the response: the
X-only norms come from ``path_engine.DESIGN_NORMS`` (once per design array
and partition), ``||X||^2`` only where a bucket is the full set, and a
session hands the engine its own X^T r0."""
import gc
import weakref

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import GroupSpec, Plan, Problem, SGLSession, path_engine
from repro.core.linalg import spectral_norm
from repro.core.path_engine import (DESIGN_NORMS, _lambda_max_sgl_jit,
                                    _xt_r0, nn_lasso_path_batched,
                                    sgl_path_batched)
from repro.core.losses import get_loss

PLAN = Plan(alpha=1.0, n_lambdas=8, min_ratio=0.1, tol=1e-8, min_bucket=16)


def _design(seed=11, N=40, sizes=None, nonneg=False):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 7, 24) if sizes is None else sizes
    spec = GroupSpec.from_sizes(sizes)
    p = spec.num_features
    X = rng.standard_normal((N, p))
    if nonneg:
        X = np.abs(X)
    return jnp.asarray(X), spec, rng


def _response(X, rng, nonneg=False):
    beta = np.zeros(X.shape[1])
    beta[:5] = 1.0 if nonneg else rng.standard_normal(5)
    return np.asarray(X) @ beta + 0.05 * rng.standard_normal(X.shape[0])


def _problem(engine, X, spec, y):
    return Problem.sgl(X, y, spec) if engine == "sgl" else \
        Problem.nn_lasso(X, y)


def _hits(res, name="setup"):
    return [s.counters.get("design_cache_hits") for s in res.spans
            if s.name == name]


def _rows(res):
    return (res.lambdas, res.betas, res.kept_features, res.iters)


def _assert_same_rows(a, b):
    for u, v in zip(_rows(a), _rows(b)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("engine,warm_hits", [("sgl", 2), ("nn", 1)])
def test_a_second_session_on_one_design_reads_the_cache(engine, warm_hits):
    nonneg = engine == "nn"
    X, spec, rng = _design(nonneg=nonneg)
    cold = SGLSession(_problem(engine, X, spec, _response(X, rng, nonneg)))
    first = cold.path(PLAN)
    y2 = _response(X, rng, nonneg)
    warm = SGLSession(_problem(engine, X, spec, y2)).path(PLAN)
    assert _hits(first) == [0] and _hits(warm) == [warm_hits]
    # the same response on a copy of the design: every norm recomputed
    X_copy = jnp.array(X, copy=True)
    fresh = SGLSession(_problem(engine, X_copy, spec, y2)).path(PLAN)
    assert _hits(fresh) == [0]
    _assert_same_rows(warm, fresh)


def test_another_partition_of_the_same_design_misses_its_group_norms():
    X, spec, rng = _design()
    y = _response(X, rng)
    SGLSession(Problem.sgl(X, y, spec)).path(PLAN)
    other = GroupSpec.from_sizes([3] * (spec.num_features // 3)
                                 + [1] * (spec.num_features % 3))
    res = SGLSession(Problem.sgl(X, y, other)).path(PLAN)
    assert _hits(res) == [1]                  # the column norms only
    cold = SGLSession(Problem.sgl(jnp.array(X, copy=True), y, other)).path(
        PLAN)
    _assert_same_rows(res, cold)


def test_a_reweighted_plan_hits_and_matches_a_cold_run():
    X, spec, rng = _design()
    y = _response(X, rng)
    sess = SGLSession(Problem.sgl(X, y, spec))
    sess.path(PLAN)
    gw = rng.uniform(0.5, 2.0, spec.num_groups)
    plan = PLAN.with_(group_weights=gw)
    warm = sess.path(plan)
    assert _hits(warm) == [2]
    cold = SGLSession(Problem.sgl(jnp.array(X, copy=True), y, spec)).path(
        plan)
    assert _hits(cold) == [0]
    _assert_same_rows(warm, cold)


def test_a_freed_design_leaves_the_cache():
    X, spec, rng = _design(seed=12)
    sess = SGLSession(Problem.sgl(X, _response(X, rng), spec))
    sess.path(PLAN.with_(screen="none"))        # a full bucket: ||X||^2 too
    values = DESIGN_NORMS._entries[id(X)][1]
    assert {"col_norms", "sq_norm"} <= set(values)
    p, G = spec.num_features, spec.num_groups
    assert all(np.size(v) in (1, p, G) for v in values.values())
    n_before = len(DESIGN_NORMS)
    ref = weakref.ref(X)
    del X, sess
    gc.collect()
    assert ref() is None                        # the cache held no reference
    assert len(DESIGN_NORMS) == n_before - 1


@pytest.mark.parametrize("engine", ["sgl", "nn"])
def test_a_full_bucket_computes_the_spectral_norm_where_it_reads_it(engine):
    nonneg = engine == "nn"
    X, spec, rng = _design(seed=13, nonneg=nonneg)
    y = _response(X, rng, nonneg)
    plan = PLAN.with_(screen="none")
    cold = SGLSession(_problem(engine, X, spec, y)).path(plan)
    rec = cold.spans
    norms = [i for i, s in enumerate(rec) if s.name == "setup.spectral_norm"]
    assert len(norms) == cold.stats.n_segments > 0
    assert all(rec[rec[i].parent].name == "segment.gather" for i in norms)
    # computed once, read from the cache by the call's later segments
    assert _hits(cold, "setup.spectral_norm") == \
        [0] + [1] * (len(norms) - 1)
    np.testing.assert_array_equal(
        np.asarray(DESIGN_NORMS._entries[id(X)][1]["sq_norm"]),
        np.asarray(spectral_norm(X) ** 2))
    warm = SGLSession(_problem(engine, X, spec, y)).path(plan)
    assert set(_hits(warm, "setup.spectral_norm")) == {1}
    _assert_same_rows(cold, warm)
    screened = SGLSession(_problem(engine, X, spec, y)).path(PLAN)
    assert "setup.spectral_norm" not in {s.name for s in screened.spans}


@pytest.mark.parametrize("loss", ["squared", "logistic"])
def test_the_sessions_xty_anchors_the_grid_as_the_engines_own(loss):
    X, spec, rng = _design(seed=14)
    y = _response(X, rng)
    if loss == "logistic":
        y = (y > np.median(y)).astype(float)
        sess = SGLSession(Problem.sgl_logistic(X, y, spec))
    else:
        sess = SGLSession(Problem.sgl(X, y, spec))
    kw = dict(n_lambdas=6, min_ratio=0.3, tol=1e-8, screen="gapsafe",
              loss=loss)
    given = sgl_path_batched(X, sess.problem.y, spec, 0.8, xty=sess._xty,
                             **kw)
    own = sgl_path_batched(X, sess.problem.y, spec, 0.8, **kw)
    np.testing.assert_allclose(given.lam_max, own.lam_max, rtol=1e-12)
    r0 = get_loss(loss).residual_at_zero(sess.problem.y)
    g_given = _lambda_max_sgl_jit(spec, sess._xty, 0.8)[1]
    g_own = _lambda_max_sgl_jit(spec, _xt_r0(X, r0), 0.8)[1]
    assert int(g_given) == int(g_own)
    np.testing.assert_array_equal(given.kept_features, own.kept_features)
    np.testing.assert_allclose(given.betas, own.betas, atol=1e-6)
    # the session's own path is the engine given the session's X^T r0
    _assert_same_rows(given, sess.path(Plan(alpha=0.8, **{
        k: v for k, v in kw.items() if k != "loss"})))


def test_the_nn_engine_given_xty_matches_its_own():
    X, _, rng = _design(seed=15, nonneg=True)
    y = _response(X, rng, nonneg=True)
    sess = SGLSession(Problem.nn_lasso(X, y))
    kw = dict(n_lambdas=6, min_ratio=0.3, tol=1e-8)
    given = nn_lasso_path_batched(X, sess.problem.y, xty=sess._xty, **kw)
    own = nn_lasso_path_batched(X, sess.problem.y, **kw)
    np.testing.assert_allclose(given.lam_max, own.lam_max, rtol=1e-12)
    np.testing.assert_array_equal(given.kept_features, own.kept_features)
    np.testing.assert_allclose(given.betas, own.betas, atol=1e-6)


@pytest.mark.parametrize("engine", ["sgl", "nn"])
def test_a_session_path_computes_no_xty_of_its_own(engine, monkeypatch):
    nonneg = engine == "nn"
    X, spec, rng = _design(seed=16, nonneg=nonneg)
    sess = SGLSession(_problem(engine, X, spec, _response(X, rng, nonneg)))

    def refuse(*_):
        raise AssertionError("the engine recomputed the session's X^T r0")

    monkeypatch.setattr(path_engine, "_xt_r0", refuse)
    assert sess.path(PLAN).stats.n_segments > 0
