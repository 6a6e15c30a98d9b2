"""The nonnegative-Lasso certificate (``dpc.nn_gap``) against a plain
float64 solver and a float64 gap, on the image-dictionary design at a
small size; the capped-row counter; and the rounds a row adds when its
bucket test passed and its full-X certificate did not.

The reference solver below is an active-set (Lawson-Hanson) method in
numpy float64, independent of the program: it solves the nonnegative
Lasso on a growing support exactly and stops at a duality gap of 1e-12
of 0.5 ||y||^2."""
import numpy as np
import jax.numpy as jnp
import pytest

from bench import catalog, data
from repro.core import Plan, Problem, SGLSession
from repro.core import path_engine as pe
from repro.core.cv import _masks_from_folds
from repro.core.dpc import dual_scaling_nn, nn_gap
from repro.core.linalg import mm
from repro.distributed import feature_shard as fs

TOL = 1e-5
PLAN = Plan(n_lambdas=35, min_ratio=0.2, tol=TOL)
# The f32 certificate's distance from the f64 gap, in units of
# tol * 0.5 ||y||^2.  A sum of nonnegative terms keeps f32's relative
# precision: measured at most 0.007 at this size; the primal-minus-dual
# subtraction misses by 0.03-0.07 here (0.07-0.12 at N = 1024).
CERT_ERROR = 0.02


# ---------------------------------------------------------------------------
# Plain float64 reference
# ---------------------------------------------------------------------------

def gap64(X, y, lam, b):
    """Duality gap of the nonnegative Lasso at b >= 0, float64: primal
    minus dual at the dual point r / lam scaled into {X^T theta <= 1}."""
    X, y, b = (np.asarray(a, np.float64) for a in (X, y, b))
    r = y - X @ b
    c = X.T @ r / lam
    s = 1.0 / c.max() if c.max() > 1.0 else 1.0
    d = y - s * r
    primal = 0.5 * r @ r + lam * b.sum()
    dual = 0.5 * y @ y - 0.5 * d @ d
    return primal - dual


def primal64(X, y, lam, b):
    X, y, b = (np.asarray(a, np.float64) for a in (X, y, b))
    r = y - X @ b
    return 0.5 * r @ r + lam * b.sum()


def nn_lasso64(X, y, lam, max_outer: int = 500):
    """min 0.5||y - Xb||^2 + lam sum(b) over b >= 0, by active sets: add
    the most violating column, solve the support's normal equations, and
    step back to the first coefficient that would turn negative."""
    X, y = np.asarray(X, np.float64), np.asarray(y, np.float64)
    p = X.shape[1]
    b = np.zeros(p)
    P = np.zeros(p, bool)
    for _ in range(max_outer):
        w = X.T @ (y - X @ b) - lam              # minus the gradient
        w[P] = -np.inf
        if w.max() <= 1e-13 * lam:
            break
        P[np.argmax(w)] = True
        while True:
            idx = np.flatnonzero(P)
            XP = X[:, idx]
            z = np.zeros(p)
            z[idx] = np.linalg.solve(XP.T @ XP, XP.T @ y - lam)
            if np.all(z[idx] > 0):
                b = z
                break
            neg = idx[z[idx] <= 0]
            step = np.min(b[neg] / (b[neg] - z[neg]))
            b = b + step * (z - b)
            P &= b > 1e-15
            b[~P] = 0.0
    assert gap64(X, y, lam, b) <= 1e-12 * 0.5 * y @ y
    return b


# ---------------------------------------------------------------------------
# Seeded image dictionaries (bench generator ``image_dictionary``)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dictionary():
    cfg = dict(catalog.config("imgdict_dpc"), n_samples=256, n_features=2000,
               basis_rank=32)
    design = data.make_design(cfg)
    Y = data.make_responses(cfg, design, 7, data.WINDOW, 0, 2)
    return design.X, Y


def _scale(y):
    return TOL * 0.5 * float(np.sum(np.asarray(y, np.float64) ** 2))


def _f32_certificates(X, y, lambdas, betas, certify):
    """(new form, old form) of each row's f32 certificate, with the
    engine's rounding: the bucket is the row's support, ``certify(rho)``
    gives X^T rho at all of X and at the bucket's columns, and s."""
    new, old = [], []
    for lam, b in zip(lambdas, betas):
        cols = np.flatnonzero(b > 0)
        beta = jnp.asarray(b[cols], jnp.float32)
        lam = jnp.float32(lam)
        r = y - mm(X[:, cols], beta)
        rho = r / lam
        c, c_cols, s = certify(rho, cols)
        new.append(float(nn_gap(r, beta, c_cols, lam, s)))
        d = y - lam * (s * rho)
        old.append(float((0.5 * jnp.vdot(r, r) + lam * jnp.sum(beta))
                         - (0.5 * jnp.vdot(y, y) - 0.5 * jnp.vdot(d, d))))
    return np.array(new), np.array(old)


def _single(X):
    def certify(rho, cols):
        c = mm(X.T, rho)
        return c, c[cols], dual_scaling_nn(c)
    return certify


def _engine_rows(kind, X, y):
    """(X, y, lambdas, betas, certify) of every row an engine returned,
    each fold's rows on the fold's masked problem."""
    sess = SGLSession(Problem.nn_lasso(X, y))
    if kind == "path":
        res = sess.path(PLAN)
        assert res.stats.n_uncertified == 0
        return [(X, y, res.lambdas, res.betas, _single(X))]
    if kind == "feature_shards":
        res = sess.path(PLAN.with_(feature_shards=4))
        assert res.stats.n_uncertified == 0
        plan = fs.plan_feature_shards(4, X.shape[1], None)
        assert plan.n_shards == 4
        ops = fs.feature_ops(plan.n_shards, None)
        Xs = ops.place(plan.stack_columns(np.asarray(X)))

        def certify(rho, cols):
            c_s, s = fs.cert_nn(ops, Xs, rho)
            return c_s, c_s.reshape(-1)[plan.stacked_index(cols)], s
        return [(X, y, res.lambdas, res.betas, certify)]
    cv = sess.cv(PLAN.with_(n_folds=3))
    assert cv.stats.n_uncertified == 0
    masks = _masks_from_folds(cv.folds, X.shape[0])
    out = []
    for k, mask in enumerate(masks):
        m = jnp.asarray(mask, X.dtype)
        Xk = X * m[:, None]
        out.append((Xk, y * m, cv.lambdas, cv.fold_betas[k], _single(Xk)))
    return out


@pytest.mark.parametrize("kind", ["path", "feature_shards", "folds"])
def test_f32_certificate_matches_the_f64_gap(dictionary, kind):
    X, Y = dictionary
    for y in Y:
        for Xk, yk, lambdas, betas, certify in _engine_rows(kind, X, y):
            new, _ = _f32_certificates(Xk, yk, lambdas, betas, certify)
            ref = np.array([gap64(Xk, yk, lam, b)
                            for lam, b in zip(lambdas, betas)])
            err = np.abs(new - ref) / _scale(yk)
            assert err.max() <= CERT_ERROR, err.max()
            # every returned row is certified: gap <= 1.01 tol, and the
            # rounding above is all that separates it from the f64 gap
            assert (ref / _scale(yk)).max() <= 1.01 + CERT_ERROR


def test_the_old_subtraction_misses_by_more(dictionary):
    X, Y = dictionary
    worst_old = worst_new = 0.0
    for y in Y:
        res = SGLSession(Problem.nn_lasso(X, y)).path(PLAN)
        new, old = _f32_certificates(X, y, res.lambdas, res.betas,
                                     _single(X))
        ref = np.array([gap64(X, y, lam, b)
                        for lam, b in zip(res.lambdas, res.betas)])
        worst_old = max(worst_old, np.abs(old - ref).max() / _scale(y))
        worst_new = max(worst_new, np.abs(new - ref).max() / _scale(y))
    assert worst_new <= CERT_ERROR < worst_old


@pytest.mark.parametrize("resp", [0, 1])
def test_engine_rows_reach_the_reference_objective(dictionary, resp):
    X, Y = dictionary
    y = Y[resp]
    res = SGLSession(Problem.nn_lasso(X, y)).path(PLAN)
    Xh, yh = np.asarray(X), np.asarray(y)
    for lam, b in zip(res.lambdas, res.betas):
        assert np.all(b >= 0)
        ref = primal64(Xh, yh, lam, nn_lasso64(Xh, yh, lam))
        excess = primal64(Xh, yh, lam, b) - ref
        assert -1e-12 * _scale(yh) <= excess <= 1.05 * _scale(yh)


def test_every_sweep_of_an_nn_path_scans_one_length(dictionary):
    """One sweep executable per bucket width: the scan length is the
    path's (34 rows below lambda_max, padded to 64), not each chunk's."""
    X, Y = dictionary
    keys = set()
    res = pe.nn_lasso_path_batched(X, Y[0], n_lambdas=35, min_ratio=0.2,
                                   tol=TOL, compile_keys=keys)
    assert {k[-2] for k in keys} == {64}
    assert {k[-3] for k in keys} == {b[0] for b in res.stats.buckets}
    assert len({b[2] for b in res.stats.buckets}) > 1     # chunk sizes vary


# ---------------------------------------------------------------------------
# Capped rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["nn", "sgl"])
def test_rows_capped_counts_the_launch_rows_at_max_iter(dictionary,
                                                        monkeypatch, kind):
    """``rows_capped`` on ``segment.sweep`` counts the rows of each launch
    whose iterations reached ``max_iter``, accepted or not."""
    max_iter = 30
    launches = []
    name = "_sweep_nn" if kind == "nn" else "_sweep_sgl"
    sweep = getattr(pe, name)

    def recorded(*args, **kw):
        out = sweep(*args, **kw)
        launches.append(np.asarray(out[4]))
        return out

    monkeypatch.setattr(pe, name, recorded)
    X, Y = dictionary
    if kind == "nn":
        prob = Problem.nn_lasso(X, Y[0])
    else:
        prob = Problem.sgl(X, Y[0], groups=[4] * (X.shape[1] // 4))
    res = SGLSession(prob).path(PLAN.with_(tol=1e-9, max_iter=max_iter,
                                           n_lambdas=12, alpha=1.0))
    counted = [s.counters["rows_capped"] for s in res.spans
               if s.name == "segment.sweep"]
    assert counted == [int(np.sum(it >= max_iter)) for it in launches]
    assert sum(counted) >= int(np.sum(res.iters >= max_iter)) > 0


# ---------------------------------------------------------------------------
# A full-X test that fails after the bucket's own test passed
# ---------------------------------------------------------------------------

def test_a_row_that_fails_the_full_test_runs_on_to_a_tighter_target(
        dictionary, monkeypatch):
    """The full-X certificate reads about 0.7 tol above the bucket's own
    gap (as a rounding difference between the two GEMVs would, only
    larger): the first solve stops at the bucket's target and fails the
    full test; the rounds to half and a quarter of it close the gap
    within ``max_iter``.  Float64, so the floor of ``effective_tol`` is
    far below every target."""
    X, Y = dictionary
    X = jnp.asarray(X[:, :300], jnp.float64)
    y = jnp.asarray(Y[0], jnp.float64)
    Xh, yh = np.asarray(X), np.asarray(y)
    lam = 0.5 * float((Xh.T @ yh).max())
    gap_scale = 0.5 * float(yh @ yh)
    # c read low by eps adds lam * s * eps * sum(b) to the gap: about
    # 0.7 tol near the optimum
    eps = 0.7 * TOL * gap_scale / (lam * nn_lasso64(Xh, yh, lam).sum())
    lip = float(np.linalg.norm(Xh, 2) ** 2)
    rounds = pe.NN_REFINE_ROUNDS

    def certify(rho):
        c = mm(X.T, rho)
        return c - eps, c - eps, dual_scaling_nn(c)

    def row(n_rounds):
        monkeypatch.setattr(pe, "NN_REFINE_ROUNDS", n_rounds)
        _, _, _, good, iters = pe._nn_rows(
            X, y, lip, jnp.asarray([lam]), jnp.asarray([True]),
            jnp.zeros(X.shape[1]), TOL, gap_scale, certify, (X.shape[1],),
            max_iter=20000, check_every=10)
        return bool(good[0]), int(iters[0])

    good, iters = row(0)
    assert not good
    good_r, iters_r = row(rounds)
    assert good_r and iters < iters_r < 20000
