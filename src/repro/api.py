"""sklearn-style estimator facade over the Problem/Plan/Session API.

The AFQ-Insight-shaped workload: fit/predict/score estimators whose ``fit``
runs K-fold model selection over a lambda grid and refits at the selected
regularization.  The classes are ``sklearn.base.BaseEstimator``s
(constructor stores hyperparameters untouched; ``fit`` sets
trailing-underscore attributes).

  SGLRegressor   one (lambda, alpha) Sparse-Group Lasso fit
  SGLClassifier  one (lambda, alpha) sparse-group LOGISTIC regression fit
                 (Gap-Safe screening from the logistic dual)
  SGLCV          fold-batched K-fold CV over the grid, then refit
  NNLassoCV      the nonnegative-Lasso analogue (DPC screening)

``BaseEstimator`` derives ``get_params`` / ``set_params`` from the
constructor signature and supplies the estimator tags, so the estimators
survive ``sklearn.base.clone`` and slot into ``GridSearchCV``.

Each CV estimator builds a ``core.Problem`` + ``core.Plan`` and runs them
through a ``core.SGLSession`` (exposed after ``fit`` as ``session_``, so
``est.session_.refine(...)`` continues warm from the CV state).  Grids are
anchored at the full-data lambda_max (``lambda_max_sgl`` /
``lambda_max_nn``); each CV fold additionally gets exact zeros above its own
per-fold lambda_max inside the fold-batched engine.

Centering: with ``fit_intercept`` the data is centered once on the full
sample before CV (``center='global'``, cheap and standard, but the held-out
rows leak into the fold means).  ``center='per-fold'`` instead scores
leakage-free models — each fold is centered by its own train-row means,
threaded through the masked-row embedding as rank-one corrections (the
final refit intercept still comes from the full sample).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from sklearn.base import BaseEstimator

from .core import (Plan, Problem, SGLSession, as_group_spec, solve_nn_lasso,
                   solve_sgl, spectral_norm)

# Backwards-compatible alias (pre-Problem/Plan name of the helper)
_as_spec = as_group_spec


def _center(X, y, fit_intercept: bool):
    X = np.asarray(X, dtype=float)
    y = np.asarray(y, dtype=float)
    if not fit_intercept:
        return X, y, np.zeros(X.shape[1]), 0.0
    x_mean = X.mean(axis=0)
    y_mean = float(y.mean())
    return X - x_mean, y - y_mean, x_mean, y_mean


class _LinearBase(BaseEstimator):
    """Shared predict/score for fitted linear models."""

    coef_: np.ndarray
    intercept_: float

    def predict(self, X):
        return np.asarray(X, dtype=float) @ self.coef_ + self.intercept_

    def score(self, X, y):
        """Coefficient of determination R^2."""
        y = np.asarray(y, dtype=float)
        resid = y - self.predict(X)
        denom = float(np.sum((y - y.mean()) ** 2))
        if denom == 0.0:
            return 0.0
        return 1.0 - float(np.sum(resid * resid)) / denom


class SGLRegressor(_LinearBase):
    """Sparse-Group Lasso at one (lam, alpha), FISTA with duality-gap stop.

    ``lam`` is the paper's lambda (l1 scale); ``alpha`` the group/l1 mix so
    the group penalty is ``alpha * lam * sum_g w_g ||beta_g||``.  ``groups``
    is a GroupSpec, a list of group sizes, or None for singleton groups.
    """

    def __init__(self, lam: float = 1.0, alpha: float = 1.0, groups=None,
                 fit_intercept: bool = True, tol: float = 1e-9,
                 max_iter: int = 20000):
        self.lam = lam
        self.alpha = alpha
        self.groups = groups
        self.fit_intercept = fit_intercept
        self.tol = tol
        self.max_iter = max_iter

    def fit(self, X, y):
        Xc, yc, x_mean, y_mean = _center(X, y, self.fit_intercept)
        spec = as_group_spec(self.groups, Xc.shape[1])
        L = float(spectral_norm(jnp.asarray(Xc))) ** 2
        res = solve_sgl(jnp.asarray(Xc), jnp.asarray(yc), spec,
                        float(self.lam), float(self.alpha), L,
                        max_iter=self.max_iter, tol=self.tol)
        self.spec_ = spec
        self.coef_ = np.asarray(res.beta)
        self.intercept_ = y_mean - float(x_mean @ self.coef_)
        self.n_iter_ = int(res.iters)
        self.dual_gap_ = float(res.gap)
        return self


class SGLClassifier(BaseEstimator):
    """Sparse-group logistic regression at one (lam, alpha).

    The SGL penalty on the binomial negative log-likelihood, solved by the
    loss-generic batched engine with Gap-Safe screening from the logistic
    dual (``screen='gapsafe'``; TLFre's variational geometry is
    squared-loss-only).  ``y`` must be 0/1 labels.  No intercept is fitted:
    centering X has no special status for the logistic likelihood — append
    a constant column if an unpenalized intercept is required.

    After ``fit``: ``coef_``, ``n_iter_``, ``kept_features_`` (columns
    surviving the screen), ``lambda_max_``, and ``session_`` (the live
    loss-generic session).  ``predict_proba`` returns ``(n, 2)`` class
    probabilities; ``score`` is classification accuracy.
    """

    def __init__(self, lam: float = 1.0, alpha: float = 1.0, groups=None,
                 screen: str = "gapsafe", tol: float = 1e-8,
                 max_iter: int = 20000):
        self.lam = lam
        self.alpha = alpha
        self.groups = groups
        self.screen = screen
        self.tol = tol
        self.max_iter = max_iter

    def fit(self, X, y):
        X = np.asarray(X, dtype=float)
        y = np.asarray(y, dtype=float)
        spec = as_group_spec(self.groups, X.shape[1])
        plan = Plan(alpha=float(self.alpha),
                    lambdas=np.asarray([float(self.lam)]),
                    screen=self.screen, tol=self.tol,
                    max_iter=self.max_iter)
        session = SGLSession(Problem.sgl_logistic(X, y, spec), plan)
        res = session.path()
        self.spec_ = spec
        self.session_ = session
        self.coef_ = np.asarray(res.betas[0])
        self.intercept_ = 0.0
        self.n_iter_ = int(res.iters[0])
        self.kept_features_ = int(res.kept_features[0])
        self.lambda_max_ = float(res.lam_max)
        return self

    def decision_function(self, X):
        return np.asarray(X, dtype=float) @ self.coef_ + self.intercept_

    def predict_proba(self, X):
        """(n, 2) class probabilities [P(y=0), P(y=1)]."""
        p1 = 1.0 / (1.0 + np.exp(-self.decision_function(X)))
        return np.stack([1.0 - p1, p1], axis=1)

    def predict(self, X):
        return (self.decision_function(X) > 0.0).astype(float)

    def score(self, X, y):
        """Classification accuracy."""
        y = np.asarray(y, dtype=float)
        return float(np.mean(self.predict(X) == y))


class SGLCV(_LinearBase):
    """Fold-batched K-fold cross-validated Sparse-Group Lasso.

    ``fit`` runs ``SGLSession.cv`` (one stacked screening GEMM per
    segment, vmapped / mesh-sharded fold sweeps), selects lambda by mean
    held-out MSE (``selection='min'``) or the 1-SE rule
    (``selection='1se'``), and refits on the full sample at the selected
    lambda.  ``center='per-fold'`` scores leakage-free per-fold-centered
    models (see the module docstring).  Exposes ``lambdas_``,
    ``mse_path_``, ``lambda_``, ``cv_result_``, and the live ``session_``
    (e.g. ``est.session_.refine(factor=10)`` for warm two-stage grid
    refinement).
    """

    def __init__(self, alpha: float = 1.0, groups=None, n_folds: int = 5,
                 n_lambdas: int = 100, min_ratio: float = 0.01,
                 lambdas=None, screen: str = "tlfre",
                 selection: str = "min", fit_intercept: bool = True,
                 center: str = "global", tol: float = 1e-9,
                 max_iter: int = 20000, safety: float = 0.0, seed: int = 0,
                 mesh=None):
        self.alpha = alpha
        self.groups = groups
        self.n_folds = n_folds
        self.n_lambdas = n_lambdas
        self.min_ratio = min_ratio
        self.lambdas = lambdas
        self.screen = screen
        self.selection = selection
        self.fit_intercept = fit_intercept
        self.center = center
        self.tol = tol
        self.max_iter = max_iter
        self.safety = safety
        self.seed = seed
        self.mesh = mesh

    def fit(self, X, y):
        Xc, yc, x_mean, y_mean = _center(X, y, self.fit_intercept)
        spec = as_group_spec(self.groups, Xc.shape[1])
        plan = Plan(alpha=float(self.alpha), lambdas=self.lambdas,
                    n_lambdas=self.n_lambdas, min_ratio=self.min_ratio,
                    screen=self.screen, tol=self.tol,
                    max_iter=self.max_iter, safety=self.safety,
                    n_folds=self.n_folds, seed=self.seed,
                    center=self.center, selection=self.selection,
                    mesh=self.mesh)
        session = SGLSession(Problem.sgl(Xc, yc, spec), plan)
        cv = session.cv()
        idx = cv.best_index if self.selection == "min" else cv.index_1se
        lam = float(cv.lambdas[idx])
        L = float(spectral_norm(jnp.asarray(Xc))) ** 2
        res = solve_sgl(jnp.asarray(Xc), jnp.asarray(yc), spec, lam,
                        float(self.alpha), L, max_iter=self.max_iter,
                        tol=self.tol)
        self.spec_ = spec
        self.session_ = session
        self.cv_result_ = cv
        self.lambdas_ = cv.lambdas
        self.mse_path_ = cv.mse_path
        self.lambda_ = lam
        self.lambda_max_ = cv.lam_max
        self.coef_ = np.asarray(res.beta)
        self.intercept_ = y_mean - float(x_mean @ self.coef_)
        self.n_iter_ = int(res.iters)
        return self


class NNLassoCV(_LinearBase):
    """Fold-batched K-fold cross-validated nonnegative Lasso (DPC)."""

    def __init__(self, n_folds: int = 5, n_lambdas: int = 100,
                 min_ratio: float = 0.01, lambdas=None, screen: str = "dpc",
                 selection: str = "min", tol: float = 1e-9,
                 max_iter: int = 20000, safety: float = 0.0, seed: int = 0,
                 mesh=None):
        self.n_folds = n_folds
        self.n_lambdas = n_lambdas
        self.min_ratio = min_ratio
        self.lambdas = lambdas
        self.screen = screen
        self.selection = selection
        self.tol = tol
        self.max_iter = max_iter
        self.safety = safety
        self.seed = seed
        self.mesh = mesh
        # no fit_intercept: centering X breaks the nonnegativity geometry

    def fit(self, X, y):
        X = np.asarray(X, dtype=float)
        y = np.asarray(y, dtype=float)
        plan = Plan(lambdas=self.lambdas, n_lambdas=self.n_lambdas,
                    min_ratio=self.min_ratio, screen=self.screen,
                    tol=self.tol, max_iter=self.max_iter,
                    safety=self.safety, n_folds=self.n_folds,
                    seed=self.seed, selection=self.selection,
                    mesh=self.mesh)
        session = SGLSession(Problem.nn_lasso(X, y), plan)
        cv = session.cv()
        idx = cv.best_index if self.selection == "min" else cv.index_1se
        lam = float(cv.lambdas[idx])
        L = float(spectral_norm(jnp.asarray(X))) ** 2
        res = solve_nn_lasso(jnp.asarray(X), jnp.asarray(y), lam, L,
                             max_iter=self.max_iter, tol=self.tol)
        self.session_ = session
        self.cv_result_ = cv
        self.lambdas_ = cv.lambdas
        self.mse_path_ = cv.mse_path
        self.lambda_ = lam
        self.lambda_max_ = cv.lam_max
        self.coef_ = np.asarray(res.beta)
        self.intercept_ = 0.0
        self.n_iter_ = int(res.iters)
        return self
