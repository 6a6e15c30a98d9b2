"""SGLSession — a persistent, device-resident execution handle binding a
``Problem`` to compiled state, so repeated runs stop paying setup again.

Why a session?  The batched engine's speed comes from three caches that the
legacy entry points rebuilt from scratch on every call:

  * **Compiled buckets.**  Sweep shapes are keyed on (fold count, feature
    bucket, group bucket, padded width, chunk length); jax's jit cache is
    process-global, so a shape compiled in ANY earlier call never
    recompiles.  The session owns one persistent key set
    (``compile_keys``) threaded through every engine call, which makes
    ``EngineStats.n_compilations`` count compilations actually *paid*: a
    second ``session.path(plan)`` over the same buckets reports zero.

  * **Grid-screen geometry.**  ``X``, ``y``, ``X^T y`` and the per-alpha
    ``lambda_max`` anchor live on device once per session instead of once
    per call.

  * **Warm duals.**  ``session.cv(plan)`` records the per-fold certified
    solutions; ``session.refine(around=lam, factor=10)`` reconstructs the
    exact per-fold duals at the nearest coarse grid point above the
    refinement window (one batched GEMM) and seeds a second, finer grid
    from them — the ROADMAP two-stage model selection.  The warm run
    screens against a reference dual that is already *near* the fine
    window (tight Theorem-12 balls) and warm-starts FISTA from the coarse
    optimum, so it converges in measurably fewer iterations than a cold
    fine-grid CV, with zero new solver compilations when the coarse run
    already visited the buckets.

Verbs: ``session.path(plan)``, ``session.cv(plan)``,
``session.refine(around=..., factor=...)``, ``session.stability(plan)``.
Each accepts a ``Plan`` (or keyword overrides applied to the session's
default plan) and returns the same result objects as the legacy surface
(``PathResult`` / ``CVResult`` / ``StabilityResult``), so downstream code
is unchanged.  ``launch/sgl_serve.py`` builds model-selection-as-a-service
on top: same-bucket jobs share one compile cache and stack their folds
into single fold-batched engine calls.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .cv import (CVResult, EngineStats, FoldState, StabilityResult,
                 _cv_statistics, _masks_from_folds, kfold_indices,
                 nn_fold_paths, per_fold_centering, sgl_fold_paths,
                 subsample_masks)
from .dpc import dual_scaling_nn, lambda_max_nn
from .groups import GroupSpec
from .lambda_max import dual_scaling_sgl, lambda_max_sgl
from .linalg import mm
from .losses import get_loss
from .path_engine import (nn_lasso_path_batched, sgl_path_batched)
from .problem import Plan, Problem


@dataclasses.dataclass
class RefineResult:
    """Outcome of a warm two-stage grid refinement (``session.refine``)."""
    coarse: CVResult             # the seeding coarse-grid CV
    fine: CVResult               # the refined-grid CV (warm-started)
    lambda_: float               # selected on the fine grid
    index: int                   # its index in fine.lambdas
    warm_start_lambda: float     # coarse grid point the duals were seeded at
    #                              (nan => window touched lam_max: cold seed)
    new_compilations: int        # sweep shapes not already in the session
    total_iters: int             # FISTA iterations summed over folds x grid


# ---------------------------------------------------------------------------
# Exact per-fold dual reconstruction (one batched GEMM per call).
# ---------------------------------------------------------------------------

@jax.jit
def _fold_duals_sgl(X, spec, alpha, Y, masks, betas, lam_ref, mus):
    """(theta, c_theta, xty, lam_max) per fold from stored grid solutions.

    ``betas`` are the certified optima at one grid point; Lemma-9 dual
    scaling of the (masked, centered) residual recovers each fold's exact
    dual there — the same algebra the engine's in-scan certification uses.
    """
    fit = mm(betas, X.T)
    if mus is not None:
        fit = fit - jnp.sum(betas * mus, axis=1)[:, None]
    resid = Y - masks * fit
    rho = resid / lam_ref
    c = mm(rho, X)
    if mus is not None:
        c = c - jnp.sum(rho, axis=1)[:, None] * mus
        xty = mm(Y, X) - jnp.sum(Y, axis=1)[:, None] * mus
    else:
        xty = mm(Y, X)
    s = jax.vmap(lambda ck: dual_scaling_sgl(spec, ck, alpha))(c)
    lam_max_f, _ = jax.vmap(lambda ck: lambda_max_sgl(spec, ck, alpha))(xty)
    return s[:, None] * rho, s[:, None] * c, xty, lam_max_f


@jax.jit
def _fold_duals_nn(X, Y, masks, betas, lam_ref):
    resid = Y - masks * mm(betas, X.T)
    rho = resid / lam_ref
    c = mm(rho, X)
    xty = mm(Y, X)
    s = jax.vmap(dual_scaling_nn)(c)
    lam_max_f, _ = jax.vmap(lambda_max_nn)(xty)
    return s[:, None] * rho, s[:, None] * c, xty, lam_max_f


@dataclasses.dataclass
class _CVState:
    """What ``refine`` needs from the last ``session.cv`` run."""
    plan: Plan
    result: CVResult
    masks: np.ndarray            # (K, N)
    y_rows: np.ndarray           # (N,) or (K, N) — responses the folds saw
    mus: Optional[np.ndarray]    # (K, p) per-fold means (center="per-fold")
    y_means: Optional[np.ndarray]
    spec: Optional[GroupSpec] = None  # effective (possibly reweighted) spec


class SGLSession:
    """Device-resident handle executing Plans against one Problem.

    >>> prob = Problem.sgl(X, y, groups=[10] * 150)
    >>> sess = SGLSession(prob)
    >>> plan = Plan(alpha=1.0, n_lambdas=40, tol=1e-8)
    >>> path = sess.path(plan)           # cold: compiles O(log p) buckets
    >>> path2 = sess.path(plan)          # warm: 0 new compilations
    >>> cv = sess.cv(plan)               # fold-batched K-fold CV
    >>> ref = sess.refine(factor=10)     # warm two-stage refinement
    """

    def __init__(self, problem: Problem, plan: Optional[Plan] = None):
        self.problem = problem
        self.default_plan = plan if plan is not None else Plan()
        self.compile_keys: set = set()   # persistent sweep-shape cache
        self.stats = EngineStats()       # aggregate over the session
        self._lam_max_cache: dict = {}   # grid-anchor cache (see lambda_max)
        if problem.loss == "squared":
            self._xty = mm(problem.X.T, problem.y)
        else:
            # the grid anchor correlates X with the gradient of the loss at
            # beta = 0 (y for squared; y - 1/2 for logistic)
            self._xty = mm(problem.X.T, get_loss(
                problem.loss).residual_at_zero(problem.y))
        self._last_cv: Optional[_CVState] = None

    # ---- plumbing ---------------------------------------------------------

    def _resolve(self, plan: Optional[Plan], overrides: dict) -> Plan:
        plan = self.default_plan if plan is None else plan
        if overrides:
            plan = plan.with_(**overrides)
        plan.validate(self.problem)
        return plan

    def _absorb(self, stats: EngineStats) -> None:
        # buckets=False: the session aggregate lives as long as the
        # session — per-segment bucket tuples would accumulate unboundedly
        self.stats.merge(stats, buckets=False)

    def _effective(self, plan: Plan):
        """(loss name, effective GroupSpec) for this plan.

        Adaptive ``plan.group_weights`` / ``plan.feature_weights`` overlay
        the problem's spec; with neither set the problem's spec object is
        returned unchanged (identity-preserving, so the default path keeps
        the exact jit cache hits of earlier sessions)."""
        loss = plan.resolved_loss(self.problem.loss)
        spec = self.problem.spec
        if spec is None:
            return loss, None
        if plan.group_weights is not None:
            gw = np.asarray(plan.group_weights, dtype=np.float64)
            if gw.shape != (spec.num_groups,):
                raise ValueError(f"group_weights must have shape "
                                 f"({spec.num_groups},), got {gw.shape}")
            if not np.all(gw > 0):
                raise ValueError("group_weights must be strictly positive")
            spec = dataclasses.replace(spec, weights=jnp.asarray(gw))
        if plan.feature_weights is not None:
            fw = np.asarray(plan.feature_weights, dtype=np.float64)
            if fw.shape != (spec.num_features,):
                raise ValueError(f"feature_weights must have shape "
                                 f"({spec.num_features},), got {fw.shape}")
            if not np.all(fw > 0):
                raise ValueError("feature_weights must be strictly positive")
            spec = dataclasses.replace(spec, feature_weights=jnp.asarray(fw))
        return loss, spec

    def lambda_max(self, alpha: float = 1.0) -> float:
        """Full-data grid anchor, cached per alpha on device-resident
        ``X^T y``."""
        if self.problem.penalty == "nn_lasso":
            key = "nn"
            if key not in self._lam_max_cache:
                self._lam_max_cache[key] = float(lambda_max_nn(self._xty)[0])
            return self._lam_max_cache[key]
        alpha = float(alpha)
        if alpha not in self._lam_max_cache:
            self._lam_max_cache[alpha] = float(lambda_max_sgl(
                self.problem.spec, self._xty, alpha)[0])
        return self._lam_max_cache[alpha]

    def _grid(self, plan: Plan, spec: Optional[GroupSpec] = None):
        """(lambdas, lam_max) under the legacy anchoring convention.
        ``spec`` (default: the problem's) anchors reweighted plans at THEIR
        lambda_max — the per-alpha cache only serves the unweighted spec."""
        if plan.lambdas is not None:
            lambdas = np.asarray(plan.lambdas, dtype=float)
            return lambdas, float(lambdas.max())
        if spec is None or spec is self.problem.spec:
            lam_max = self.lambda_max(plan.alpha)
        else:
            lam_max = float(lambda_max_sgl(spec, self._xty, plan.alpha)[0])
        if self.problem.penalty == "nn_lasso" and lam_max <= 0:
            raise ValueError("max_i <x_i, y> <= 0: nonnegative Lasso "
                             "solution is identically zero")
        return plan.grid(lam_max), lam_max

    # ---- verbs ------------------------------------------------------------

    def path(self, plan: Optional[Plan] = None, **overrides):
        """Solve one lambda path; compiled buckets persist across calls."""
        plan = self._resolve(plan, overrides)
        prob = self.problem
        loss, spec = self._effective(plan)
        screen = plan.resolved_screen(prob.penalty, loss)
        if plan.engine == "legacy":
            from .path import nn_lasso_path, sgl_path
            if prob.penalty == "sgl":
                return sgl_path(
                    prob.X, prob.y, spec, plan.alpha,
                    lambdas=plan.lambdas, n_lambdas=plan.n_lambdas,
                    min_ratio=plan.min_ratio, screen=screen, tol=plan.tol,
                    max_iter=plan.max_iter, safety=plan.safety,
                    specnorm_method=plan.specnorm_method,
                    check_every=plan.check_every)
            return nn_lasso_path(
                prob.X, prob.y, lambdas=plan.lambdas,
                n_lambdas=plan.n_lambdas, min_ratio=plan.min_ratio,
                screen=screen, tol=plan.tol, max_iter=plan.max_iter,
                safety=plan.safety, check_every=plan.check_every)
        if prob.penalty == "sgl":
            res = sgl_path_batched(
                prob.X, prob.y, spec, plan.alpha,
                lambdas=plan.lambdas, n_lambdas=plan.n_lambdas,
                min_ratio=plan.min_ratio, screen=screen, tol=plan.tol,
                max_iter=plan.max_iter, safety=plan.safety,
                specnorm_method=plan.specnorm_method,
                check_every=plan.check_every, use_pallas=plan.use_pallas,
                min_bucket=plan.min_bucket,
                min_group_bucket=plan.min_group_bucket, margin=plan.margin,
                chunk_init=plan.chunk_init,
                feature_shards=plan.feature_shards,
                compile_keys=self.compile_keys, loss=loss)
        else:
            res = nn_lasso_path_batched(
                prob.X, prob.y, lambdas=plan.lambdas,
                n_lambdas=plan.n_lambdas, min_ratio=plan.min_ratio,
                screen=screen, tol=plan.tol, max_iter=plan.max_iter,
                safety=plan.safety, check_every=plan.check_every,
                use_pallas=plan.use_pallas, min_bucket=plan.min_bucket,
                margin=plan.margin, chunk_init=plan.chunk_init,
                feature_shards=plan.feature_shards,
                compile_keys=self.compile_keys)
        self._absorb(res.stats)
        return res

    def _fold_setup(self, plan: Plan):
        """(folds, masks, mus, y_means, y_rows) for this plan's CV."""
        prob = self.problem
        N = prob.n_samples
        folds = (plan.folds if plan.folds is not None
                 else kfold_indices(N, plan.n_folds, plan.seed))
        masks = _masks_from_folds(folds, N)
        y_np = np.asarray(prob.y, dtype=float)
        if plan.center == "per-fold":
            mus, y_means, y_rows = per_fold_centering(
                np.asarray(prob.X, dtype=float), y_np, masks)
        else:
            mus = y_means = None
            y_rows = y_np
        return folds, masks, mus, y_means, y_rows

    def cv(self, plan: Optional[Plan] = None, **overrides) -> CVResult:
        """Fold-batched K-fold CV; records warm state for ``refine``."""
        plan = self._resolve(plan, overrides)
        prob = self.problem
        loss, spec = self._effective(plan)
        screen = plan.resolved_screen(prob.penalty, loss)
        lambdas, lam_max = self._grid(plan, spec)
        folds, masks, mus, y_means, y_rows = self._fold_setup(plan)
        if prob.penalty == "sgl":
            betas, kept, iters, stats, times = sgl_fold_paths(
                prob.X, y_rows, spec, plan.alpha, masks, lambdas,
                screen=screen, tol=plan.tol, max_iter=plan.max_iter,
                safety=plan.safety, specnorm_method=plan.specnorm_method,
                check_every=plan.check_every, min_bucket=plan.min_bucket,
                min_group_bucket=plan.min_group_bucket, margin=plan.margin,
                chunk_init=plan.chunk_init, chunk_cap=plan.chunk_cap,
                schedule=plan.schedule, use_pallas=plan.use_pallas,
                mesh=plan.mesh, mus=mus, compile_keys=self.compile_keys,
                feature_shards=plan.feature_shards, loss=loss)
        else:
            betas, kept, iters, stats, times = nn_fold_paths(
                prob.X, y_rows, masks, lambdas, screen=screen, tol=plan.tol,
                max_iter=plan.max_iter, safety=plan.safety,
                check_every=plan.check_every, min_bucket=plan.min_bucket,
                margin=plan.margin, chunk_init=plan.chunk_init,
                chunk_cap=plan.chunk_cap, schedule=plan.schedule,
                use_pallas=plan.use_pallas, mesh=plan.mesh,
                compile_keys=self.compile_keys,
                feature_shards=plan.feature_shards)
        res = _cv_statistics(np.asarray(prob.X), np.asarray(prob.y), folds,
                             np.asarray(lambdas, float), betas, lam_max,
                             kept, stats, times, iters=iters, mus=mus,
                             y_means=y_means)
        self._absorb(stats)
        self._last_cv = _CVState(plan=plan, result=res, masks=masks,
                                 y_rows=y_rows, mus=mus, y_means=y_means,
                                 spec=spec)
        return res

    def _fold_state_at(self, j_ref: int) -> FoldState:
        """Exact per-fold engine state at coarse grid point ``j_ref``,
        reconstructed from the stored certified solutions (one batched
        GEMM; a fold whose own lambda_max sits below the reference is
        clamped to its exact all-zero lambda_max state)."""
        st = self._last_cv
        prob = self.problem
        coarse = st.result
        lam_ref = float(coarse.lambdas[j_ref])
        masks_d = jnp.asarray(st.masks, prob.dtype)
        K, N = st.masks.shape
        y_rows = np.broadcast_to(np.asarray(st.y_rows, dtype=float),
                                 (K, N))
        Y = masks_d * jnp.asarray(y_rows, prob.dtype)
        betas = jnp.asarray(coarse.fold_betas[:, j_ref], prob.dtype)
        mus_d = (None if st.mus is None
                 else jnp.asarray(st.mus, prob.dtype))
        if prob.penalty == "sgl":
            spec = st.spec if st.spec is not None else prob.spec
            theta, c_theta, xty, lam_max_f = _fold_duals_sgl(
                prob.X, spec, st.plan.alpha, Y, masks_d, betas,
                lam_ref, mus_d)
        else:
            theta, c_theta, xty, lam_max_f = _fold_duals_nn(
                prob.X, Y, masks_d, betas, lam_ref)
        # np.array, not asarray: device arrays view as read-only and the
        # at-max branch below rewrites rows in place
        theta = np.array(theta, dtype=float)
        c_theta = np.array(c_theta, dtype=float)
        xty = np.asarray(xty, dtype=float)
        lam_max_f = np.asarray(lam_max_f, dtype=float)
        beta0 = np.asarray(coarse.fold_betas[:, j_ref], dtype=float).copy()
        lam_bar = np.full(K, lam_ref)
        at_max = lam_ref >= lam_max_f * (1.0 - 1e-12)
        for k in np.nonzero(at_max)[0]:
            # the reference sits at/above this fold's own lambda_max: its
            # exact state there is the all-zero solution with dual y/lam
            lm = lam_max_f[k] if lam_max_f[k] > 0 else 1.0
            lam_bar[k] = lm
            theta[k] = st.masks[k] * y_rows[k] / lm
            c_theta[k] = xty[k] / lm
            beta0[k] = 0.0
        return FoldState(lam_bar=lam_bar, theta=theta, c_theta=c_theta,
                         beta=beta0)

    def refine(self, around: Optional[float] = None, factor: float = 10.0,
               n_lambdas: Optional[int] = None,
               plan: Optional[Plan] = None, **overrides) -> RefineResult:
        """Warm two-stage grid refinement around the CV-selected lambda.

        Runs a fine grid of ``n_lambdas`` points spanning ``factor``
        (log-spaced, centered on ``around`` — default: the lambda the last
        ``session.cv`` selected under the plan's selection rule), seeded
        from the coarse run's certified per-fold duals at the nearest
        coarse grid point above the window.  Returns the fine-grid
        ``CVResult`` plus warm-start accounting.
        """
        if self._last_cv is None:
            raise RuntimeError("session.refine requires a prior "
                               "session.cv(plan) on this session")
        st = self._last_cv
        base = st.plan if plan is None else plan
        plan = base.with_(**overrides) if overrides else base
        plan.validate(self.problem)
        # the warm state is only exact for the coarse run's geometry: the
        # reconstructed duals are feasible for the coarse alpha's dual set,
        # and masks/centering are reused from the coarse run — reject plans
        # that silently change either
        changed = [f for f in ("alpha", "center", "n_folds", "seed", "loss")
                   if getattr(plan, f) != getattr(st.plan, f)]
        for f in ("folds", "group_weights", "feature_weights"):
            if getattr(plan, f) is not getattr(st.plan, f):
                changed.append(f)
        if changed:
            raise ValueError(
                f"refine cannot change {changed} (the warm per-fold state "
                f"is only exact for the coarse run's geometry) — run "
                f"session.cv with the new plan instead")
        coarse = st.result
        if around is None:
            around = (coarse.best_lambda if plan.selection == "min"
                      else coarse.lambda_1se)
        if factor <= 1.0:
            raise ValueError("factor must be > 1")
        half = math.sqrt(factor)
        hi = min(around * half, coarse.lam_max * (1.0 - 1e-9))
        lo = min(around / half, hi)
        n = int(n_lambdas) if n_lambdas is not None else plan.n_lambdas
        fine = np.exp(np.linspace(math.log(hi), math.log(lo), n))

        above = np.nonzero(coarse.lambdas >= hi * (1.0 - 1e-12))[0]
        if len(above):
            j_ref = int(above[-1])     # nearest coarse point above the window
            init = self._fold_state_at(j_ref)
            warm_lam = float(coarse.lambdas[j_ref])
        else:                          # window touches lam_max: cold seed
            init, warm_lam = None, float("nan")

        prob = self.problem
        loss, spec = self._effective(plan)
        screen = plan.resolved_screen(prob.penalty, loss)
        if prob.penalty == "sgl":
            betas, kept, iters, stats, times = sgl_fold_paths(
                prob.X, st.y_rows, spec, plan.alpha, st.masks, fine,
                screen=screen, tol=plan.tol, max_iter=plan.max_iter,
                safety=plan.safety, specnorm_method=plan.specnorm_method,
                check_every=plan.check_every, min_bucket=plan.min_bucket,
                min_group_bucket=plan.min_group_bucket, margin=plan.margin,
                chunk_init=plan.chunk_init, chunk_cap=plan.chunk_cap,
                schedule=plan.schedule, use_pallas=plan.use_pallas,
                mesh=plan.mesh, mus=st.mus, init=init,
                compile_keys=self.compile_keys,
                feature_shards=plan.feature_shards, loss=loss)
        else:
            betas, kept, iters, stats, times = nn_fold_paths(
                prob.X, st.y_rows, st.masks, fine, screen=screen,
                tol=plan.tol, max_iter=plan.max_iter, safety=plan.safety,
                check_every=plan.check_every, min_bucket=plan.min_bucket,
                margin=plan.margin, chunk_init=plan.chunk_init,
                chunk_cap=plan.chunk_cap, schedule=plan.schedule,
                use_pallas=plan.use_pallas, mesh=plan.mesh, init=init,
                compile_keys=self.compile_keys,
                feature_shards=plan.feature_shards)
        fine_res = _cv_statistics(np.asarray(prob.X), np.asarray(prob.y),
                                  coarse.folds, fine, betas, coarse.lam_max,
                                  kept, stats, times, iters=iters,
                                  mus=st.mus, y_means=st.y_means)
        self._absorb(stats)
        # the refined run becomes the new warm state: refine() composes
        self._last_cv = _CVState(plan=plan, result=fine_res, masks=st.masks,
                                 y_rows=st.y_rows, mus=st.mus,
                                 y_means=st.y_means, spec=spec)
        idx = (fine_res.best_index if plan.selection == "min"
               else fine_res.index_1se)
        return RefineResult(
            coarse=coarse, fine=fine_res, lambda_=float(fine[idx]),
            index=idx, warm_start_lambda=warm_lam,
            new_compilations=stats.n_compilations,
            total_iters=int(np.sum(iters)))

    def stability(self, plan: Optional[Plan] = None,
                  **overrides) -> StabilityResult:
        """Selection probabilities over random row-subsamples, batched
        through the fold engine with the session's compile cache."""
        plan = self._resolve(plan, overrides)
        prob = self.problem
        if prob.penalty != "sgl":
            raise ValueError("stability selection is implemented for the "
                             "SGL penalty")
        loss, spec = self._effective(plan)
        screen = plan.resolved_screen("sgl", loss)
        lambdas, _ = self._grid(plan, spec)
        N, p = prob.n_samples, prob.n_features
        masks = subsample_masks(N, plan.n_subsamples, plan.subsample_frac,
                                plan.seed)
        counts = np.zeros((len(lambdas), p))
        agg = EngineStats()
        for b0 in range(0, plan.n_subsamples, plan.batch_size):
            betas, _, _, stats, _ = sgl_fold_paths(
                prob.X, prob.y, spec, plan.alpha,
                masks[b0:b0 + plan.batch_size], lambdas, screen=screen,
                tol=plan.tol, max_iter=plan.max_iter, safety=plan.safety,
                specnorm_method=plan.specnorm_method,
                check_every=plan.check_every, min_bucket=plan.min_bucket,
                min_group_bucket=plan.min_group_bucket, margin=plan.margin,
                chunk_init=plan.chunk_init, chunk_cap=plan.chunk_cap,
                schedule=plan.schedule, use_pallas=plan.use_pallas,
                mesh=plan.mesh, compile_keys=self.compile_keys,
                feature_shards=plan.feature_shards, loss=loss)
            counts += (np.abs(betas) > plan.active_tol).sum(axis=0)
            agg.merge(stats, buckets=False)
        self._absorb(agg)
        probs = counts / plan.n_subsamples
        return StabilityResult(lambdas=np.asarray(lambdas, float),
                               selection_probs=probs,
                               max_probs=probs.max(axis=0),
                               n_subsamples=plan.n_subsamples, stats=agg)
