"""Loss-generic core: bit-identity on squared loss, certified logistic
paths, adaptive penalty weights, and the sklearn estimator protocol.

The refactor contract has two halves:

  1. Squared loss is an IDENTITY transformation — float64 ``session.path``
     / ``session.cv`` outputs match the pre-refactor golden snapshot
     (``tests/data/make_golden.py``): lambda grids exactly, betas and
     held-out errors at the certificate scale ``tol = 1e-9``.  Another XLA
     build reorders f64 reductions; the drift seen is <= 1.25e-11 on 142
     of 1200 path betas (bound: ``tol``), <= 1e-14 on the nonnegative
     Lasso betas, and <= 2.7e-8 relative on the held-out errors (bound:
     ``100 * tol``).
  2. The new surface is correct — logistic paths carry full-problem
     duality-gap certificates, adaptive weights move ``lambda_max`` and
     the prox exactly, the fold drivers refuse losses that break the
     masked-row embedding, and the estimators survive ``sklearn.base.clone``.
"""
import importlib.util
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.api import NNLassoCV, SGLClassifier, SGLCV, SGLRegressor
from repro.core import (GroupSpec, LOGISTIC, SQUARED, Plan, Problem,
                        SGLSession, dual_scaling_sgl, get_loss,
                        lambda_max_sgl, sgl_penalty, solve_sgl,
                        spectral_norm)

_DATA = os.path.join(os.path.dirname(__file__), "data")


def _make_golden_module():
    spec = importlib.util.spec_from_file_location(
        "make_golden", os.path.join(_DATA, "make_golden.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _logistic_problem(seed=0, N=60, G=10, n=4):
    rng = np.random.default_rng(seed)
    p = G * n
    X = rng.standard_normal((N, p))
    beta = np.zeros(p)
    for g in rng.choice(G, 3, replace=False):
        beta[g * n:g * n + 2] = rng.standard_normal(2)
    y = (X @ beta + 0.5 * rng.standard_normal(N) > 0).astype(float)
    return X, y, GroupSpec.uniform_groups(G, n)


# ---------------------------------------------------------------------------
# 1. Bit-identity: squared loss through the refactored engine
# ---------------------------------------------------------------------------

def test_squared_session_bit_identical_to_golden():
    """f64 path/CV/nn outputs match the pre-refactor snapshot: grids
    exactly, betas to ``tol`` and held-out errors to ``100 * tol``."""
    mg = _make_golden_module()
    g = np.load(os.path.join(_DATA, "golden_squared.npz"))

    X, y, spec = mg.make_problem()
    plan = Plan(alpha=0.9, n_lambdas=20, min_ratio=0.05, tol=1e-9,
                max_iter=20000, n_folds=3, seed=0)
    sess = SGLSession(Problem.sgl(X, y, spec), plan)
    path = sess.path()
    cv = sess.cv()
    np.testing.assert_array_equal(np.asarray(path.lambdas),
                                  g["path_lambdas"])
    cert = dict(rtol=0.0, atol=plan.tol)
    np.testing.assert_allclose(np.asarray(path.betas), g["path_betas"],
                               **cert)
    np.testing.assert_array_equal(np.asarray(cv.lambdas), g["cv_lambdas"])
    np.testing.assert_allclose(np.asarray(cv.mse_path), g["cv_mse_path"],
                               rtol=100 * plan.tol, atol=0.0)
    np.testing.assert_allclose(np.asarray(cv.mean_mse), g["cv_mean_mse"],
                               rtol=100 * plan.tol, atol=0.0)

    rng = np.random.default_rng(1)
    Xn = np.abs(rng.standard_normal((30, 40)))
    bn = np.zeros(40)
    bn[:5] = np.abs(rng.standard_normal(5))
    yn = Xn @ bn + 0.01 * rng.standard_normal(30)
    sess_nn = SGLSession(Problem.nn_lasso(Xn, yn),
                         Plan(n_lambdas=15, min_ratio=0.05, tol=1e-9))
    path_nn = sess_nn.path()
    np.testing.assert_array_equal(np.asarray(path_nn.lambdas),
                                  g["nn_lambdas"])
    np.testing.assert_allclose(np.asarray(path_nn.betas), g["nn_betas"],
                               **cert)


def test_plan_weight_overlay_matches_explicit_weighted_spec():
    """``Plan(group_weights=..., feature_weights=...)`` on a plain-spec
    session is bit-identical to baking the weights into the GroupSpec."""
    mg = _make_golden_module()
    X, y, _ = mg.make_problem(seed=5)
    G, n = 15, 4
    rng = np.random.default_rng(6)
    gw = rng.uniform(0.5, 2.0, G)
    fw = rng.uniform(0.5, 2.0, G * n)
    base = Plan(alpha=0.8, n_lambdas=10, min_ratio=0.1, tol=1e-10)

    plain = SGLSession(Problem.sgl(X, y, GroupSpec.uniform_groups(G, n)))
    res_a = plain.path(base.with_(group_weights=gw, feature_weights=fw))
    spec_w = GroupSpec.from_sizes([n] * G, weights=gw, feature_weights=fw)
    res_b = SGLSession(Problem.sgl(X, y, spec_w)).path(base)
    np.testing.assert_array_equal(np.asarray(res_a.lambdas),
                                  np.asarray(res_b.lambdas))
    np.testing.assert_array_equal(np.asarray(res_a.betas),
                                  np.asarray(res_b.betas))


# ---------------------------------------------------------------------------
# 2. Logistic paths: certified gaps, screening parity, fold refusal
# ---------------------------------------------------------------------------

def test_logistic_path_certifies_every_grid_point():
    """Every accepted logistic solution carries a full-problem duality-gap
    certificate at the solver tolerance (recomputed here from scratch)."""
    X, y, spec = _logistic_problem(3)
    tol = 1e-8
    prob = Problem.sgl_logistic(X, y, spec)
    plan = Plan(alpha=0.9, n_lambdas=10, min_ratio=0.1, tol=tol,
                max_iter=50_000)
    res = SGLSession(prob, plan).path()
    Xj, yj = jnp.asarray(X), jnp.asarray(y)
    scale = LOGISTIC.gap_scale_host(yj)
    for j in range(len(res.lambdas)):
        lam = float(res.lambdas[j])
        beta = jnp.asarray(res.betas[j])
        fit = Xj @ beta
        resid = LOGISTIC.residual(yj, fit)
        s = dual_scaling_sgl(spec, Xj.T @ (resid / lam), 0.9)
        theta = s * resid / lam
        pval = (float(LOGISTIC.primal_value(yj, fit, resid))
                + lam * float(sgl_penalty(spec, beta, 0.9)))
        dval = float(LOGISTIC.dual_value(yj, theta, lam))
        assert pval - dval <= 2.0 * tol * scale


def test_logistic_screened_equals_unscreened():
    X, y, spec = _logistic_problem(4)
    kw = dict(alpha=1.0, n_lambdas=10, min_ratio=0.1, tol=1e-10,
              max_iter=50_000)
    prob = Problem.sgl_logistic(X, y, spec)
    res_s = SGLSession(prob).path(Plan(screen="gapsafe", **kw))
    res_b = SGLSession(prob).path(Plan(screen="none", **kw))
    np.testing.assert_allclose(res_s.betas, res_b.betas, atol=5e-6)


def test_logistic_fold_paths_refuse_masked_embedding():
    """The fold drivers embed folds as zero-masked rows; logistic rows do
    not vanish at zero (f(0,0)=log 2), so CV must refuse loudly."""
    X, y, spec = _logistic_problem(5)
    sess = SGLSession(Problem.sgl_logistic(X, y, spec),
                      Plan(n_lambdas=5, min_ratio=0.2, n_folds=3))
    with pytest.raises(NotImplementedError, match="masked"):
        sess.cv()


def test_logistic_rejects_tlfre_screen():
    from repro.core.path_engine import sgl_path_batched
    X, y, spec = _logistic_problem(6)
    with pytest.raises(ValueError, match="tlfre"):
        sgl_path_batched(X, y, spec, 1.0, n_lambdas=5, screen="tlfre",
                         loss="logistic")


def test_f32_logistic_path_keeps_certificates():
    """Satellite: the dtype-aware tolerance floor lives in the Loss — an
    f32 logistic run with an unreachable tol certifies at the floor
    instead of spinning every solve to max_iter."""
    assert float(LOGISTIC.effective_tol(1e-12, jnp.float32)) == \
        64.0 * float(jnp.finfo(jnp.float32).eps)
    assert float(LOGISTIC.effective_tol(1e-6, jnp.float64)) == 1e-6
    X, y, spec = _logistic_problem(7)
    max_iter = 5000
    res = SGLSession(
        Problem.sgl_logistic(np.asarray(X, np.float32),
                             np.asarray(y, np.float32), spec,
                             dtype=np.float32),
        Plan(n_lambdas=8, min_ratio=0.15, tol=1e-12,
             max_iter=max_iter)).path()
    assert np.all(np.asarray(res.iters) < max_iter)


# ---------------------------------------------------------------------------
# 3. Adaptive weights: lambda_max boundary + prox correctness
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_weighted_lambda_max_is_exact_boundary(seed):
    """At the weighted ``lambda_max`` the all-zero solution is optimal;
    just below it is not."""
    rng = np.random.default_rng(seed)
    G, n, N = 8, 3, 40
    p = G * n
    X = rng.standard_normal((N, p))
    y = X[:, 0] + 0.1 * rng.standard_normal(N)
    spec = GroupSpec.from_sizes([n] * G,
                                weights=rng.uniform(0.5, 2.0, G),
                                feature_weights=rng.uniform(0.5, 2.0, p))
    alpha = 0.7
    lam_max = float(lambda_max_sgl(spec, jnp.asarray(X.T @ y), alpha)[0])
    L = float(spectral_norm(jnp.asarray(X))) ** 2
    above = solve_sgl(jnp.asarray(X), jnp.asarray(y), spec,
                      1.001 * lam_max, alpha, L, tol=1e-12,
                      max_iter=50_000)
    assert float(jnp.max(jnp.abs(above.beta))) == 0.0
    below = solve_sgl(jnp.asarray(X), jnp.asarray(y), spec,
                      0.95 * lam_max, alpha, L, tol=1e-12,
                      max_iter=50_000)
    assert float(jnp.max(jnp.abs(below.beta))) > 0.0


# ---------------------------------------------------------------------------
# 4. SGLClassifier vs an independent reference solver
# ---------------------------------------------------------------------------

def _ref_logistic_fista(X, y, spec, lam, alpha, iters=20_000):
    """Plain-numpy FISTA on the sparse-group logistic objective — the
    prox is written out from the definitions, sharing nothing with the
    package's solver."""
    sizes = np.asarray(spec.sizes)
    starts = np.asarray(spec.starts)
    w = np.asarray(spec.weights)
    L = 0.25 * np.linalg.norm(X, 2) ** 2
    t = 1.0 / L
    p = X.shape[1]
    beta = np.zeros(p)
    z = beta.copy()
    tk = 1.0
    for _ in range(iters):
        u = X @ z
        grad = X.T @ (1.0 / (1.0 + np.exp(-u)) - y)
        v = z - t * grad
        nxt = np.sign(v) * np.maximum(np.abs(v) - t * lam, 0.0)
        for k in range(len(sizes)):
            s0, sz = int(starts[k]), int(sizes[k])
            seg = nxt[s0:s0 + sz]
            ng = np.linalg.norm(seg)
            thr = t * lam * alpha * w[k]
            nxt[s0:s0 + sz] = (0.0 if ng <= thr
                               else seg * (1.0 - thr / ng))
        tk_next = 0.5 * (1.0 + np.sqrt(1.0 + 4.0 * tk * tk))
        z = nxt + ((tk - 1.0) / tk_next) * (nxt - beta)
        beta, tk = nxt, tk_next
    return beta


def _logistic_objective(X, y, spec, lam, alpha, beta):
    u = X @ beta
    nll = float(np.sum(np.logaddexp(0.0, u) - y * u))
    pen = float(sgl_penalty(spec, jnp.asarray(beta), alpha))
    return nll + lam * pen


def test_classifier_matches_reference_solver():
    X, y, spec = _logistic_problem(8, N=60, G=10, n=4)
    xty = np.asarray(jnp.asarray(X).T @ (jnp.asarray(y) - 0.5))
    alpha = 0.8
    lam = 0.3 * float(lambda_max_sgl(spec, jnp.asarray(xty), alpha)[0])
    clf = SGLClassifier(lam=lam, alpha=alpha, groups=[4] * 10, tol=1e-10,
                        max_iter=100_000).fit(X, y)
    ref = _ref_logistic_fista(X, y, spec, lam, alpha)
    obj_clf = _logistic_objective(X, y, spec, lam, alpha, clf.coef_)
    obj_ref = _logistic_objective(X, y, spec, lam, alpha, ref)
    assert obj_clf <= obj_ref + 1e-6
    np.testing.assert_allclose(clf.coef_, ref, atol=1e-3)
    assert clf.score(X, y) > 0.5
    proba = clf.predict_proba(X)
    assert proba.shape == (len(y), 2)
    np.testing.assert_allclose(proba.sum(axis=1), 1.0, atol=1e-12)


# ---------------------------------------------------------------------------
# 5. sklearn estimator protocol
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("est", [
    SGLRegressor(lam=0.4, alpha=0.6, groups=[2, 3]),
    SGLClassifier(lam=0.4, alpha=0.6, groups=[2, 3]),
    SGLCV(alpha=0.6, n_folds=3),
    NNLassoCV(n_folds=3),
])
def test_get_set_params_roundtrip(est):
    params = est.get_params()
    assert params == type(est)(**params).get_params()
    est.set_params(**params)
    with pytest.raises(ValueError, match="(?i)invalid parameter"):
        est.set_params(definitely_not_a_param=1)


def test_estimators_survive_sklearn_clone():
    sklearn = pytest.importorskip("sklearn")
    from sklearn.base import clone
    est = SGLClassifier(lam=0.25, alpha=0.5, groups=[4] * 10, tol=1e-6)
    cl = clone(est)
    assert cl is not est
    assert cl.get_params() == est.get_params()


def test_classifier_in_grid_search():
    pytest.importorskip("sklearn")
    from sklearn.model_selection import GridSearchCV
    X, y, _ = _logistic_problem(9, N=60, G=10, n=4)
    xty = np.asarray(jnp.asarray(X).T @ (jnp.asarray(y) - 0.5))
    spec = GroupSpec.uniform_groups(10, 4)
    lam_max = float(lambda_max_sgl(spec, jnp.asarray(xty), 1.0)[0])
    grid = GridSearchCV(
        SGLClassifier(alpha=1.0, groups=[4] * 10, tol=1e-6,
                      max_iter=5000),
        {"lam": [0.5 * lam_max, 0.2 * lam_max]}, cv=2)
    grid.fit(X, y)
    assert grid.best_params_["lam"] in (0.5 * lam_max, 0.2 * lam_max)
    assert 0.0 <= grid.best_score_ <= 1.0
