"""Fold-parallel model selection on the batched engine: K-fold CV and
stability selection for SGL / nonnegative Lasso.

The paper makes *one* lambda path cheap; the canonical consumer of repeated
grid solves is K-fold cross-validation (pick lambda by held-out error) and
stability selection (selection probabilities over random subsamples).  Both
are the same workload: solve the SAME grid on K row-subsets of one design
matrix.  This module runs all K subset paths simultaneously, device-resident:

  * **Masked-row embedding.**  Fold k's training problem is the full-size
    problem with its held-out rows zeroed: every per-fold vector
    (response, dual iterate, normal direction, residual) lives on the full
    row index with zeros at the validation rows.  Zero rows contribute
    nothing to any inner product, so the masked algebra IS the per-fold
    algebra — and every fold shares the one (N, p) design matrix.

  * **Fold-batched grid screening.**  At each scheduler step the ready
    folds' ball geometries (Theorem 12 per fold) are stacked into a single
    ``(K*L, N) x (N, p)`` GEMM against the shared design
    (``tlfre_screen_grid_folds`` / ``dpc_screen_grid_folds``) — one MXU
    launch screens every (fold, lambda) pair.  ``EngineStats.n_screens``
    counts these stacked GEMMs: one per scheduler step, NOT one per fold.
    On float32 problems the screening reductions run through the fused
    fold-stack Pallas kernels (``kernels.ops.screen_norms_folds`` /
    ``dpc_screen_folds``) — counted in ``EngineStats.n_pallas_screens``;
    float64 exactness runs never engage the float32 kernels.

  * **Fold-batched sweeps.**  The per-segment speculative ``lax.scan``
    sweep of the single-fold engine (``path_engine.sweep_sgl_core``) is
    vmapped over a leading fold axis on a COMMON feature bucket (the max
    of the cohort's per-fold buckets), carrying each fold's warm-started
    coefficients.  Every fold still certifies every accepted row against
    its own full training problem, so per-fold results match independent
    single-fold paths to solver precision.  With a multi-device mesh the
    fold axis is sharded via ``shard_map``
    (``launch.mesh.make_fold_mesh`` / ``shard_over_folds``); on one device
    the vmap runs as-is.

  * **Elastic fold scheduling** (``schedule='elastic'``, the default).
    Folds no longer advance in lockstep segments.  Each fold carries its
    own speculative chunk length (doubling on fully-certified chunks,
    throttling only itself on a failed certificate), ready folds are
    grouped into cohorts of like chunk length, and each cohort is
    dispatched as its own asynchronous sweep launch: a fast fold that
    certified its whole chunk is screened and re-dispatched immediately
    while a slow fold's launch is still in flight.
    ``jax.block_until_ready`` is deferred until a launch is harvested —
    and harvesting prefers launches whose certificates are already
    materialised.  ``schedule='lockstep'`` restores the single-cohort
    segment loop (one launch at a time, one shared chunk length) for A/B
    benchmarking.

Under vmap the in-scan ``lax.cond`` row-kill lowers to ``select`` (both
branches execute), so a failed certificate still gates *acceptance* but no
longer saves the dead rows' compute — under elastic scheduling that waste is
confined to the slow fold's own (short) cohort instead of padding every
fold's rows to the same chunk.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import spans
from .dpc import dpc_screen_grid_folds, gap_safe_screen_grid_nn, lambda_max_nn
from .fenchel import shrink, weighted_l1
from .groups import GroupSpec, group_norms
from .lambda_max import lambda_max_sgl
from .losses import SQUARED, Loss, get_loss
from .linalg import group_spectral_norms, mm, spectral_norm
from .path import _bucket
from .path_engine import (EngineStats, _expand_set, _feature_bucket,
                          _pallas_active, _pow2_len, bucket_columns,
                          margin_fill_nn, margin_fill_sgl, sweep_nn_core,
                          sweep_sgl_core)
from .dpc import dpc_screen_grid_folds_feat
from .screening import (gap_safe_grid_radii, gap_safe_screen_grid_folds,
                        gap_safe_screen_grid_folds_feat,
                        tlfre_screen_grid_folds, tlfre_screen_grid_folds_feat)

SCHEDULES = ("elastic", "lockstep")


# ---------------------------------------------------------------------------
# Fold bookkeeping
# ---------------------------------------------------------------------------

def kfold_indices(n_samples: int, n_folds: int, seed: int = 0):
    """Deterministic shuffled K-fold split.

    Returns a list of ``(train_idx, val_idx)`` pairs.  Validation sets are
    disjoint, cover ``range(n_samples)``, and their sizes differ by at most
    one; the same ``(n_samples, n_folds, seed)`` always yields the same
    split.
    """
    if not 2 <= n_folds <= n_samples:
        raise ValueError(f"need 2 <= n_folds <= n_samples, got "
                         f"{n_folds} / {n_samples}")
    perm = np.random.default_rng(seed).permutation(n_samples)
    sizes = np.full(n_folds, n_samples // n_folds, dtype=int)
    sizes[: n_samples % n_folds] += 1
    folds = []
    off = 0
    for s in sizes:
        val = np.sort(perm[off:off + s])
        off += s
        train = np.setdiff1d(np.arange(n_samples), val)
        folds.append((train, val))
    return folds


def subsample_masks(n_samples: int, n_subsamples: int, frac: float = 0.5,
                    seed: int = 0) -> np.ndarray:
    """(B, N) 0/1 masks of random row subsamples (stability selection)."""
    rng = np.random.default_rng(seed)
    m = max(1, int(round(frac * n_samples)))
    masks = np.zeros((n_subsamples, n_samples))
    for b in range(n_subsamples):
        masks[b, rng.choice(n_samples, m, replace=False)] = 1.0
    return masks


def _masks_from_folds(folds, n_samples: int) -> np.ndarray:
    masks = np.zeros((len(folds), n_samples))
    for k, (train, _) in enumerate(folds):
        masks[k, train] = 1.0
    return masks


def per_fold_centering(X_np, y_np, masks):
    """Leakage-free per-fold centering statistics on the masked embedding.

    Returns ``(mus (K, p), y_means (K,), y_rows (K, N))``: each fold's
    train-row column means, response mean, and the response centered by its
    own fold mean.  One definition shared by ``SGLSession.cv`` and the
    serving front-end so the centering algebra cannot drift between them.
    """
    n_train = masks.sum(axis=1)
    mus = (masks @ X_np) / n_train[:, None]
    y_means = (masks @ y_np) / n_train
    return mus, y_means, y_np[None, :] - y_means[:, None]


@dataclasses.dataclass
class CVResult:
    lambdas: np.ndarray          # (J,) common grid (shared across folds)
    fold_betas: np.ndarray       # (K, J, p) per-fold solutions on the grid
    mse_path: np.ndarray         # (K, J) held-out MSE per fold
    mean_mse: np.ndarray         # (J,)
    se_mse: np.ndarray           # (J,) standard error over folds
    best_index: int              # argmin of mean_mse
    best_lambda: float
    index_1se: int               # largest lambda within 1 SE of the min
    lambda_1se: float
    folds: list                  # [(train_idx, val_idx)] actually used
    lam_max: float               # full-data lambda_max (grid anchor)
    kept_features: np.ndarray    # (K, J) solver columns per fold/lambda
    stats: EngineStats
    screen_time: float
    solve_time: float
    setup_time: float
    fold_iters: np.ndarray = None  # (K, J) FISTA iterations per fold/lambda

    @property
    def total_time(self):
        return self.screen_time + self.solve_time + self.setup_time


@dataclasses.dataclass
class FoldState:
    """Exact per-fold warm state at a reference lambda (one row per fold).

    This is the carry the fold-batched engine threads between segments,
    exported so ``SGLSession.refine`` can seed a second, finer grid from a
    coarse run's certified duals instead of refitting from lambda_max."""
    lam_bar: np.ndarray          # (K,) reference lambda per fold
    theta: np.ndarray            # (K, N) exact dual at lam_bar, masked
    c_theta: np.ndarray          # (K, p) X_train^T theta (centered design)
    beta: np.ndarray             # (K, p) primal optimum at lam_bar


@dataclasses.dataclass
class StabilityResult:
    lambdas: np.ndarray          # (J,)
    selection_probs: np.ndarray  # (J, p) P[feature active] over subsamples
    max_probs: np.ndarray        # (p,) max over the grid (Meinshausen-
    #                              Buhlmann stable set score)
    n_subsamples: int
    stats: EngineStats


# ---------------------------------------------------------------------------
# Jitted fold-batched screens (one stacked GEMM per call)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("screen", "use_pallas"))
def _screen_folds_sgl(X, Y, spec, alpha, rem, lam_bars, lam_maxs, theta_bars,
                      n_bound, beta_prev, c_prev, masks, col_n_f, gspec_f,
                      safety, mus, *, screen: str, use_pallas: bool):
    """Stacked TLFre (+ optional Gap-Safe) screen for K folds x L lambdas.

    All per-fold arrays are masked to their training rows.  Exactly one
    ``(K*L, N) x (N, p)`` GEMM is issued (inside
    ``tlfre_screen_grid_folds``); the Gap-Safe intersection adds only
    GEMV-sized work because each fold's dynamic ball center is fixed
    across the grid.  ``mus`` (None, or (K, p) per-fold column means)
    applies the leakage-free centering rank-one corrections without
    breaking the shared-design GEMM.  ``use_pallas`` routes the group-stat
    reductions through the fused fold-stack kernel (f32 only).  Returns
    feat_keep (K, L, p).
    """
    at_max = (lam_bars >= lam_maxs * (1.0 - 1e-12))[:, None]
    n_vecs = jnp.where(at_max, n_bound, Y / lam_bars[:, None] - theta_bars)
    _, fk, _ = tlfre_screen_grid_folds(X, Y, spec, alpha, rem, theta_bars,
                                       n_vecs, col_n_f, gspec_f,
                                       safety=safety, mus=mus,
                                       use_pallas=use_pallas)
    if screen == "gapsafe":
        fit = mm(beta_prev, X.T)
        if mus is not None:     # centered fit: (X - 1 mu^T) beta
            fit = fit - jnp.sum(beta_prev * mus, axis=1)[:, None]
        resid = Y - masks * fit
        if spec.feature_weights is None:
            l1 = jnp.sum(jnp.abs(beta_prev), axis=1)
        else:
            l1 = jax.vmap(lambda b: weighted_l1(spec, b))(beta_prev)
        pen = (alpha * jnp.sum(spec.weights.astype(X.dtype)[None, :]
                               * jax.vmap(lambda b: group_norms(spec, b))(
                                   beta_prev), axis=1)
               + l1)
        radii = jax.vmap(gap_safe_grid_radii)(Y, rem, theta_bars, resid,
                                              pen) * (1.0 + safety)
        _, fk_dyn = gap_safe_screen_grid_folds(spec, alpha, c_prev, radii,
                                               col_n_f, gspec_f,
                                               use_pallas=use_pallas)
        fk = fk & fk_dyn
    return fk


@functools.partial(jax.jit, static_argnames=("screen", "use_pallas"))
def _screen_folds_nn(X, Y, rem, lam_bars, lam_maxs, theta_bars, n_bound,
                     beta_prev, c_prev, masks, col_n_f, safety, *,
                     screen: str, use_pallas: bool):
    """Stacked DPC (+ optional Gap-Safe) screen; one GEMM for all folds."""
    at_max = (lam_bars >= lam_maxs * (1.0 - 1e-12))[:, None]
    n_vecs = jnp.where(at_max, n_bound, Y / lam_bars[:, None] - theta_bars)
    fk, _ = dpc_screen_grid_folds(X, Y, rem, theta_bars, n_vecs, col_n_f,
                                  safety=safety, use_pallas=use_pallas)
    if screen == "gapsafe":
        resid = Y - masks * mm(beta_prev, X.T)
        pen = jnp.sum(beta_prev, axis=1)         # beta >= 0 => l1 = sum
        radii = jax.vmap(gap_safe_grid_radii)(Y, rem, theta_bars, resid,
                                              pen) * (1.0 + safety)
        fk = fk & jax.vmap(gap_safe_screen_grid_nn)(c_prev, radii, col_n_f)
    return fk


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("screen",))
def _screen_folds_sgl_feat(fops, Xs, Y, spec, specs_s, alpha, rem, lam_bars,
                           lam_maxs, theta_bars, n_bound, beta_prev, beta_s,
                           c_prev_s, masks, col_n_sf, gspec_sf, safety,
                           mus_s, *, screen: str):
    """Feature-sharded ``_screen_folds_sgl``: the (K*L, N) x (N, p) screen
    GEMM runs per column block (no collective); the Gap-Safe intersection's
    fit is the one psum.  The penalty term uses the replicated full
    ``beta_prev`` with the GLOBAL spec (O(K p), no X involved), so the radii
    match the unsharded screen's.  Returns feat_keep (S, K, L, p_shard)."""
    from ..distributed.feature_shard import sharded_fit
    at_max = (lam_bars >= lam_maxs * (1.0 - 1e-12))[:, None]
    n_vecs = jnp.where(at_max, n_bound, Y / lam_bars[:, None] - theta_bars)
    _, fk_s, _ = tlfre_screen_grid_folds_feat(
        fops, Xs, specs_s, Y, alpha, rem, theta_bars, n_vecs, col_n_sf,
        gspec_sf, safety=safety, mus_s=mus_s)
    if screen == "gapsafe":
        if mus_s is None:
            fit = sharded_fit(fops, Xs, beta_s)
        else:
            def body(loc):
                Xb, bb, mub = loc
                return mm(bb, Xb.T), jnp.sum(bb * mub, axis=1)
            fit, corr = fops.fsum(body, (Xs, beta_s, mus_s))
            fit = fit - corr[:, None]
        resid = Y - masks * fit
        pen = (alpha * jnp.sum(spec.weights.astype(Xs.dtype)[None, :]
                               * jax.vmap(lambda b: group_norms(spec, b))(
                                   beta_prev), axis=1)
               + jnp.sum(jnp.abs(beta_prev), axis=1))
        radii = jax.vmap(gap_safe_grid_radii)(Y, rem, theta_bars, resid,
                                              pen) * (1.0 + safety)
        _, fk_dyn_s = gap_safe_screen_grid_folds_feat(
            fops, specs_s, alpha, c_prev_s, radii, col_n_sf, gspec_sf)
        fk_s = fk_s & fk_dyn_s
    return fk_s


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("screen",))
def _screen_folds_nn_feat(fops, Xs, Y, rem, lam_bars, lam_maxs, theta_bars,
                          n_bound, beta_prev, beta_s, c_prev_s, masks,
                          col_n_sf, safety, *, screen: str):
    """Feature-sharded ``_screen_folds_nn``.  Returns (S, K, L, p_shard)."""
    from ..distributed.feature_shard import sharded_fit
    at_max = (lam_bars >= lam_maxs * (1.0 - 1e-12))[:, None]
    n_vecs = jnp.where(at_max, n_bound, Y / lam_bars[:, None] - theta_bars)
    fk_s, _ = dpc_screen_grid_folds_feat(fops, Xs, Y, rem, theta_bars,
                                         n_vecs, col_n_sf, safety=safety)
    if screen == "gapsafe":
        resid = Y - masks * sharded_fit(fops, Xs, beta_s)
        pen = jnp.sum(beta_prev, axis=1)         # beta >= 0 => l1 = sum
        radii = jax.vmap(gap_safe_grid_radii)(Y, rem, theta_bars, resid,
                                              pen) * (1.0 + safety)

        def body(loc, radii):
            ct, cn = loc
            return jax.vmap(gap_safe_screen_grid_nn)(ct, radii, cn)

        fk_s = fk_s & fops.fmap(body, (c_prev_s, col_n_sf), radii)
    return fk_s


# ---------------------------------------------------------------------------
# Fold-batched sweeps: vmap over the fold axis, shard_map across the mesh
# ---------------------------------------------------------------------------

_SGL_SWEEP_AXES = (None, 0, 0, None, 0, None, 0, 0, 0, 0, None, 0)
_NN_SWEEP_AXES = (None, 0, 0, 0, 0, 0, 0, 0, None, 0)
_FOLD_SWEEPS: dict = {}


def _fold_sweep(kind: str, mesh, n_folds: int, max_iter: int,
                check_every: int, centered: bool = False,
                use_pallas: bool = False, loss: Loss = SQUARED):
    """Jitted fold-batched sweep, cached per (kind, mesh, statics).

    vmaps the single-fold segment sweep over a leading fold axis; when a
    multi-device 'fold' mesh is supplied and the cohort size divides it
    (``launch.mesh.fold_shard_compatible`` — elastic cohorts fluctuate, so
    the check runs per launch), the fold axis is sharded across it with
    ``shard_map``.  ``centered`` adds the per-fold column-mean argument
    (axis 0) for leakage-free per-fold centering; ``use_pallas`` routes the
    FISTA prox and certification GEMV through the fused f32 kernels.
    ``loss`` (SGL only) swaps the smooth data-fit term of the sweep core.
    """
    core, axes = ((sweep_sgl_core, _SGL_SWEEP_AXES) if kind == "sgl"
                  else (sweep_nn_core, _NN_SWEEP_AXES))
    if centered:
        axes = axes + (0,)
    from ..launch.mesh import fold_shard_compatible
    use_shard = fold_shard_compatible(mesh, n_folds)
    # Mesh hashes by devices+axes, so equal meshes from repeated
    # make_fold_mesh calls share one cache entry (id() would re-trace per
    # call and pin dead meshes forever)
    key = (kind, mesh if use_shard else None, max_iter, check_every,
           centered, use_pallas, loss.name)
    fn = _FOLD_SWEEPS.get(key)
    if fn is None:
        kwargs = dict(max_iter=max_iter, check_every=check_every,
                      use_pallas=use_pallas)
        if kind == "sgl":
            kwargs["loss"] = loss
        f = jax.vmap(functools.partial(core, **kwargs), in_axes=axes)
        if use_shard:
            from ..launch.mesh import shard_over_folds
            f = shard_over_folds(f, mesh, axes)
        fn = _FOLD_SWEEPS[key] = jax.jit(f)
    return fn


def _stack_specs(specs):
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *specs)


_spectral_norms_f = jax.jit(jax.vmap(
    lambda A: spectral_norm(A, iters=25) ** 2))


# ---------------------------------------------------------------------------
# Chunk policies
# ---------------------------------------------------------------------------

def _build_rem(lambdas, j_pos, act):
    """Per-active-fold remaining grids, padded to a common pow2 length by
    repeating each fold's last lambda (extra rows are screened and
    discarded on the host slice)."""
    J = len(lambdas)
    Lp = _pow2_len(int((J - j_pos[act]).max()))
    rem = np.empty((len(act), Lp))
    for i, k in enumerate(act):
        r = lambdas[j_pos[k]:]
        rem[i, :len(r)] = r
        rem[i, len(r):] = r[-1]
    return rem


def _next_chunk_len(spec_m, accepted, limited=None, cap: int = 64):
    """Lockstep chunk policy: double the shared speculative chunk when
    every fold certified everything; otherwise throttle to the slowest
    fold's accepted prefix.

    ``limited`` flags folds whose chunk was capped by their REMAINING GRID
    rather than by the speculative budget — they are finishing their path,
    and a partial certificate on a 1-2 row tail chunk used to drag every
    other fold's chunk back to 2 for the rest of the path.  Grid-limited
    folds are excluded from both the all-certified check and the throttle
    minimum; with every fold grid-limited the chunk doubles (the pool is
    draining)."""
    if limited is None:
        limited = [False] * len(accepted)
    free = [ab for ab, lim in zip(accepted, limited) if not lim]
    if all(a == b for a, b in free):
        return min(2 * spec_m, cap)
    return max(2, min(a for a, b in free if a < b))


def _next_fold_chunk(chunk: int, kk: int, mk: int, cap: int) -> int:
    """Elastic per-fold chunk policy: a fold that certified its whole chunk
    doubles ITS OWN chunk; a failed certificate throttles only that fold.
    No fold's pace ever feeds back into another fold's chunk."""
    if kk == mk:
        return min(2 * max(chunk, 1), cap)
    return max(2, kk)


# ---------------------------------------------------------------------------
# The shared fold scheduler.  The SGL and NN drivers differ in screening
# math and bucketed-subproblem construction; the grid bookkeeping, the
# fully-screened-prefix advance, the certified-prefix acceptance, the chunk
# policies and the launch queue are identical and correctness-critical, so
# they live here exactly once.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Launch:
    """One dispatched (possibly still in-flight) fold-batched sweep."""
    sweep: list          # [(k, fkk, mk, limited)] cohort members
    col_idxs: list       # per-member solver column indices
    lam_pads: np.ndarray  # (Ka, len2) padded lambda chunks
    outputs: tuple       # (betas, thetas, cthetas, good, iters) device arrays
    p_b: int
    g_b: int


class _FoldEngine:
    """Shared scheduler state + acceptance logic for the fold drivers.

    Subclasses provide ``_screen_call(act, rem)`` (the penalty-specific
    stacked grid screen, one GEMM) and ``make_launch(cohort)`` (bucketed
    subproblems + one vmapped sweep dispatch, non-blocking).  ``run`` owns
    the grid cursors, the chunk policies, and the launch queue;
    ``screen`` wraps ``_screen_call`` with the shared padding/accounting."""

    def __init__(self, X, masks_np, y_rows_np, lambdas, lam_max_np, xty_np,
                 *, tol, max_iter, safety, check_every, min_bucket, margin,
                 mesh, pallas, screen_mode, stats, seen_keys):
        self.X = X
        self.X_np = np.asarray(X)
        self.N, self.p = X.shape
        self.masks_np = masks_np
        self.y_rows_np = y_rows_np
        self.lambdas = lambdas
        self.J = len(lambdas)
        self.K = masks_np.shape[0]
        self.lam_max_np = lam_max_np
        self.xty_np = xty_np
        self.tol = tol
        self.max_iter = max_iter
        self.safety = safety
        self.check_every = check_every
        self.min_bucket = min_bucket
        self.margin = margin
        self.mesh = mesh
        self.pallas = pallas
        self.screen_mode = screen_mode
        self.stats = stats
        self.seen_keys = seen_keys
        self.screen_time = 0.0
        self.solve_time = 0.0
        # feature sharding (screens only — sweeps keep full-X certification);
        # subclasses populate these when a FeatureShardPlan is supplied
        self.fshard = None
        self.fops = None
        self.Xs = None

        K, J, p = self.K, self.J, self.p
        lam_max_safe = np.where(lam_max_np > 0, lam_max_np, 1.0)
        self.Theta = masks_np * y_rows_np / lam_max_safe[:, None]
        self.Cprev = xty_np / lam_max_safe[:, None]
        self.lam_bar = lam_max_safe.copy()
        self.Beta = np.zeros((K, p))
        self.j_pos = np.zeros(K, dtype=int)
        self.betas_out = np.zeros((K, J, p))
        self.iters_out = np.zeros((K, J), dtype=np.int64)
        self.kept_out = np.zeros((K, J), dtype=np.int64)
        self.gap_scales = np.maximum(
            0.5 * np.sum((masks_np * y_rows_np) ** 2, axis=1), 1e-30)

    def load_init(self, init: FoldState) -> None:
        """Seed the warm-start chain from an exact per-fold reference state
        (``SGLSession.refine``)."""
        self.lam_bar = np.asarray(init.lam_bar, dtype=float).copy()
        self.Theta = np.asarray(init.theta, dtype=float).copy()
        self.Cprev = np.asarray(init.c_theta, dtype=float).copy()
        self.Beta = np.asarray(init.beta, dtype=float).copy()

    # -- shared pieces -------------------------------------------------------

    def advance_zero_prefix(self, k: int, counts: np.ndarray) -> None:
        """Fully-screened prefix for fold k: beta* = 0 on those grid points
        and the exact dual optimum is y/lam, so the fold advances without
        solving."""
        adv = int(np.argmax(counts > 0)) if counts.any() else len(counts)
        lam_new = float(self.lambdas[self.j_pos[k] + adv - 1])
        self.lam_bar[k] = lam_new
        self.Theta[k] = self.masks_np[k] * self.y_rows_np[k] / lam_new
        self.Cprev[k] = self.xty_np[k] / lam_new
        self.Beta[k] = 0.0
        self.j_pos[k] += adv

    def screen(self, act: np.ndarray) -> np.ndarray:
        """One stacked grid screen over the ready folds' remaining grids:
        a single ``(K*L, N) x (N, p)`` GEMM inside the penalty-specific
        ``_screen_call``, with the padding, timing, host sync and
        ``EngineStats`` accounting shared here."""
        rem = _build_rem(self.lambdas, self.j_pos, act)
        if self.screen_mode == "none":
            return np.ones((len(act), rem.shape[1], self.p), dtype=bool)
        with spans.span("fold.screen") as sp:
            fk_np = np.asarray(self._screen_call(act, rem))  # one host sync
            self.stats.n_screens += 1                        # ONE GEMM issued
            # the sharded screen route is jnp-only — the fused fold-stack
            # kernels only ever run on the unsharded path
            self.stats.n_pallas_screens += int(self.pallas
                                               and self.fshard is None)
        self.screen_time += sp.seconds
        return fk_np

    def harvest(self, launch: _Launch):
        """Accept each fold's certified prefix and carry its exact dual
        forward.  Blocks on the launch's certificates (the one mandatory
        host sync per launch); the heavy outputs are sliced per fold to the
        accepted rows only, so rejected speculative rows are never
        transferred.  Row 0 of every fold is solved on a provably safe
        superset, so kk >= 1 guarantees progress; a row 0 that stopped at
        ``max_iter`` uncertified is kept and counted in ``n_uncertified``."""
        with spans.span("fold.solve") as sp:
            betas_b, thetas_b, cthetas_b, good_b, iters_b = launch.outputs
            good_np = np.asarray(good_b)                 # one host sync
            accepted = []
            for t, (k, _, mk, limited) in enumerate(launch.sweep):
                good = good_np[t][:mk]
                kk = int(np.argmin(good)) if not good.all() else mk
                if kk == 0:
                    kk = 1
                    self.stats.n_uncertified += 1
                self.stats.n_rejected += int(mk - kk)
                col_idx = launch.col_idxs[t]
                rows = np.zeros((kk, self.p))
                rows[:, col_idx] = np.asarray(betas_b[t, :kk, :len(col_idx)])
                j0 = self.j_pos[k]
                self.betas_out[k, j0:j0 + kk] = rows
                self.iters_out[k, j0:j0 + kk] = np.asarray(iters_b[t, :kk])
                self.kept_out[k, j0:j0 + kk] = len(col_idx)
                self.Beta[k] = rows[-1]
                self.Theta[k] = np.asarray(thetas_b[t, kk - 1])
                self.Cprev[k] = np.asarray(cthetas_b[t, kk - 1])
                self.lam_bar[k] = float(launch.lam_pads[t, kk - 1])
                self.j_pos[k] += kk
                accepted.append((k, kk, mk, limited))
        self.solve_time += sp.seconds
        self.stats.buckets.append(
            (launch.p_b, launch.g_b, max(mk for _, _, mk, _ in launch.sweep),
             min(kk for _, kk, _, _ in accepted)))
        return accepted

    @staticmethod
    def _pick_launch(inflight: list, schedule: str) -> _Launch:
        """Oldest launch — except under elastic scheduling, prefer one
        whose certificates are already materialised on device so the block
        lands on a launch that actually finished (deferred
        ``block_until_ready``)."""
        if schedule == "elastic" and len(inflight) > 1:
            for i, launch in enumerate(inflight):
                is_ready = getattr(launch.outputs[3], "is_ready", None)
                if is_ready is not None and is_ready():
                    return inflight.pop(i)
        return inflight.pop(0)

    # -- the scheduler loop --------------------------------------------------

    def run(self, schedule: str, chunk_init: int, chunk_cap: int) -> None:
        """Drive every fold through the grid.

        Lockstep: one cohort per step containing every ready fold, one
        shared chunk length (``_next_chunk_len``), dispatch immediately
        followed by harvest — the PR-2 segment loop.  Elastic: per-fold
        chunk lengths (``_next_fold_chunk``), ready folds grouped into
        cohorts of like chunk length, each cohort its own asynchronous
        launch; a fold is screened and re-dispatched as soon as ITS launch
        is harvested, while slower cohorts keep sweeping in flight."""
        K, J = self.K, self.J
        j_pos = self.j_pos
        spec_m = max(int(chunk_init), 1)              # lockstep shared chunk
        chunk = np.full(K, max(int(chunk_init), 1), dtype=int)
        busy = np.zeros(K, dtype=bool)
        inflight: list = []
        fold_sweeps = np.zeros(K, dtype=np.int64)

        def pace(k):
            return _pow2_len(int(chunk[k]))

        while (j_pos < J).any() or inflight:
            ready = np.nonzero((j_pos < J) & ~busy)[0]
            if schedule == "elastic" and len(ready) and busy.any():
                # pace hysteresis: a ready fold whose chunk is within 2x
                # of an IN-FLIGHT fold's waits one harvest so the two
                # re-merge into a single launch — like-paced folds keep
                # the lockstep cadence, while a fold whose pace genuinely
                # diverged (>2x chunk ratio) dispatches immediately and
                # never gates anyone
                busy_cls = {pace(b) for b in np.nonzero(busy)[0]}
                ready = np.asarray(
                    [k for k in ready
                     if not any(c // 2 <= pace(k) <= 2 * c
                                for c in busy_cls)], dtype=int)
            sweep = []
            if len(ready):
                fk_np = self.screen(ready)            # ONE stacked GEMM
                for i, k in enumerate(ready):
                    fkk = fk_np[i][:J - j_pos[k]]
                    counts = fkk.sum(axis=1)
                    if counts[0] == 0:
                        self.advance_zero_prefix(k, counts)
                        continue
                    budget = spec_m if schedule == "lockstep" else \
                        int(chunk[k])
                    mk = min(J - j_pos[k], budget)
                    sweep.append((k, fkk, mk, mk < budget))
            if sweep:
                if schedule == "lockstep":
                    cohorts = [sweep]
                else:
                    # cohorts greedily band folds within a 2x chunk ratio:
                    # a cohort's folds share the launch's scan length, so
                    # only like-paced folds pad each other's rows (bounded
                    # 2x) and a genuinely slow fold gets its own launch
                    entries = sorted(sweep, key=lambda e: -pace(e[0]))
                    cohorts = []
                    for e in entries:
                        if cohorts and 2 * pace(e[0]) >= \
                                pace(cohorts[-1][0][0]):
                            cohorts[-1].append(e)
                        else:
                            cohorts.append([e])
                for cohort in cohorts:
                    inflight.append(self.make_launch(cohort))
                    self.stats.n_segments += 1
                    for k, _, _, _ in cohort:
                        busy[k] = True
                        fold_sweeps[k] += 1
            if inflight:
                launch = self._pick_launch(inflight, schedule)
                accepted = self.harvest(launch)
                limited_flags = [lim for _, _, _, lim in accepted]
                for k, kk, mk, _ in accepted:
                    busy[k] = False
                    if schedule == "elastic":
                        chunk[k] = _next_fold_chunk(int(chunk[k]), kk, mk,
                                                    chunk_cap)
                if schedule == "lockstep":
                    spec_m = _next_chunk_len(
                        spec_m, [(kk, mk) for _, kk, mk, _ in accepted],
                        limited_flags, cap=chunk_cap)
        self.stats.fold_sweeps = fold_sweeps


class _SGLFoldEngine(_FoldEngine):
    """SGL screening (TLFre / Gap-Safe) + group-bucketed sweeps."""

    def __init__(self, *args, spec, alpha, Y, masks_d, col_n_f, gspec_f,
                 lam_max_f, n_bound, mus_d, mus_np,
                 min_group_bucket: int = 16, fshard=None,
                 loss: Loss = SQUARED, **kw):
        super().__init__(*args, **kw)
        self.spec = spec
        self.alpha = alpha
        self.loss = loss
        self.fw_np = (None if spec.feature_weights is None
                      else np.asarray(spec.feature_weights))
        self.Y = Y
        self.masks_d = masks_d
        self.col_n_f = col_n_f
        self.gspec_f = gspec_f
        self.lam_max_f = lam_max_f
        self.n_bound = n_bound
        self.mus_d = mus_d
        self.mus_np = mus_np
        self.centered = mus_d is not None
        self.G = spec.num_groups
        self.gid = np.asarray(spec.group_ids)
        self.sizes_np = np.asarray(spec.sizes)
        self.weights_np = np.asarray(spec.weights)
        self.min_group_bucket = min_group_bucket
        if fshard is not None:
            from ..distributed import feature_shard as _fs
            self.fshard = fshard
            self.fops = _fs.feature_ops(
                fshard.n_shards, _fs.resolve_feature_mesh(fshard.n_shards))
            self.Xs = self.fops.place(fshard.stack_columns(self.X_np))
            self.specs_s = fshard.specs_stacked
            self.col_n_sf = jnp.asarray(
                fshard.shard_features(np.asarray(col_n_f)))
            self.gspec_sf = jnp.asarray(
                fshard.shard_groups(np.asarray(gspec_f)))
            self.mus_sf = (jnp.asarray(fshard.shard_features(
                np.asarray(mus_d))) if self.centered else None)

    def _screen_call(self, act: np.ndarray, rem: np.ndarray):
        a_idx = jnp.asarray(act)
        X = self.X
        if self.fshard is not None:
            fk_s = _screen_folds_sgl_feat(
                self.fops, self.Xs, self.Y[a_idx], self.spec, self.specs_s,
                self.alpha, jnp.asarray(rem, X.dtype),
                jnp.asarray(self.lam_bar[act], X.dtype),
                self.lam_max_f[a_idx],
                jnp.asarray(self.Theta[act], X.dtype), self.n_bound[a_idx],
                jnp.asarray(self.Beta[act], X.dtype),
                jnp.asarray(self.fshard.shard_features(
                    self.Beta[act].astype(self.X_np.dtype))),
                jnp.asarray(self.fshard.shard_features(
                    self.Cprev[act].astype(self.X_np.dtype))),
                self.masks_d[a_idx], self.col_n_sf[:, a_idx],
                self.gspec_sf[:, a_idx], self.safety,
                self.mus_sf[:, a_idx] if self.centered else None,
                screen=self.screen_mode)
            return self.fshard.unshard_features(np.asarray(fk_s))
        return _screen_folds_sgl(
            X, self.Y[a_idx], self.spec, self.alpha,
            jnp.asarray(rem, X.dtype),
            jnp.asarray(self.lam_bar[act], X.dtype), self.lam_max_f[a_idx],
            jnp.asarray(self.Theta[act], X.dtype), self.n_bound[a_idx],
            jnp.asarray(self.Beta[act], X.dtype),
            jnp.asarray(self.Cprev[act], X.dtype), self.masks_d[a_idx],
            self.col_n_f[a_idx], self.gspec_f[a_idx], self.safety,
            self.mus_d[a_idx] if self.centered else None,
            screen=self.screen_mode, use_pallas=self.pallas)

    def make_launch(self, cohort) -> _Launch:
        with spans.span("fold.solve") as sp:
            N, p, G = self.N, self.p, self.G
            p_b = max(_feature_bucket(int(fkk[0].sum()), p, self.min_bucket,
                                      self.margin)
                      for _, fkk, _, _ in cohort)
            S_list = [_expand_set(fkk[0], fkk, p_b) for _, fkk, _, _ in cohort]
            g_b = min(max(_bucket(len(np.unique(self.gid[S])) + 2,
                                  self.min_group_bucket)
                          for S in S_list), G + 1)
            for (k, _, _, _), S in zip(cohort, S_list):
                # same margin rule as the single-fold engine, per-fold c_prev
                margin_fill_sgl(S, self.Cprev[k], self.gid, self.sizes_np,
                                self.weights_np, p_b, g_b, self.fw_np)

            Ka = len(cohort)
            m_ks = [mk for _, _, mk, _ in cohort]
            len2 = _pow2_len(max(m_ks))
            X_subs = np.zeros((Ka, N, p_b), dtype=self.X_np.dtype)
            beta0s = np.zeros((Ka, p_b), dtype=self.X_np.dtype)
            lam_pads = np.zeros((Ka, len2))
            valids = np.zeros((Ka, len2), dtype=bool)
            sub_specs = []
            col_idxs = []
            for t, ((k, _, mk, _), S) in enumerate(zip(cohort, S_list)):
                sub_spec, col_idx = self.spec.bucketed_subset(S, p_b, g_b)
                cols = self.X_np[:, col_idx]
                if self.centered:
                    cols = cols - self.mus_np[k][col_idx][None, :]
                X_subs[t, :, :len(col_idx)] = cols * self.masks_np[k][:, None]
                beta0s[t, :len(col_idx)] = self.Beta[k][col_idx]
                chunk = self.lambdas[self.j_pos[k]:self.j_pos[k] + mk]
                lam_pads[t, :mk] = chunk
                lam_pads[t, mk:] = chunk[-1]
                valids[t, :mk] = True
                sub_specs.append(sub_spec)
                col_idxs.append(col_idx)
            X = self.X
            X_subs_d = jnp.asarray(X_subs)
            L_subs = _spectral_norms_f(X_subs_d)
            # cover every jit-cache-discriminating dim: persistent compile_keys
            # sets span calls (and, in serving, problems of different N/dtype)
            key = ("sgl-folds", Ka, N, p, G, str(X.dtype), self.max_iter,
                   self.check_every, self.mesh, p_b, g_b, self.spec.max_size,
                   len2, self.centered, self.pallas, self.loss.name)
            if key not in self.seen_keys:
                self.seen_keys.add(key)
                self.stats.n_compilations += 1
            k_rows = jnp.asarray(np.asarray([k for k, _, _, _ in cohort]))
            runner = _fold_sweep("sgl", self.mesh, Ka, self.max_iter,
                                 self.check_every, self.centered, self.pallas,
                                 loss=self.loss)
            sweep_args = [
                X, X_subs_d, self.Y[k_rows], self.spec,
                _stack_specs(sub_specs),
                self.alpha, L_subs, jnp.asarray(lam_pads, X.dtype),
                jnp.asarray(valids), jnp.asarray(beta0s), self.tol,
                jnp.asarray(self.gap_scales[[k for k, _, _, _ in cohort]],
                            X.dtype)]
            if self.centered:
                sweep_args.append(self.mus_d[k_rows])
            outputs = runner(*sweep_args)            # asynchronous dispatch
        self.solve_time += sp.seconds
        return _Launch(sweep=cohort, col_idxs=col_idxs, lam_pads=lam_pads,
                       outputs=outputs, p_b=p_b, g_b=g_b)


class _NNFoldEngine(_FoldEngine):
    """Nonnegative-Lasso screening (DPC / Gap-Safe) + flat-bucket sweeps."""

    def __init__(self, *args, Y, masks_d, col_n_f, lam_max_f, n_bound,
                 fshard=None, **kw):
        super().__init__(*args, **kw)
        self.Y = Y
        self.masks_d = masks_d
        self.col_n_f = col_n_f
        self.lam_max_f = lam_max_f
        self.n_bound = n_bound
        if fshard is not None:
            from ..distributed import feature_shard as _fs
            self.fshard = fshard
            self.fops = _fs.feature_ops(
                fshard.n_shards, _fs.resolve_feature_mesh(fshard.n_shards))
            self.Xs = self.fops.place(fshard.stack_columns(self.X_np))
            self.col_n_sf = jnp.asarray(
                fshard.shard_features(np.asarray(col_n_f)))

    def _screen_call(self, act: np.ndarray, rem: np.ndarray):
        a_idx = jnp.asarray(act)
        X = self.X
        if self.fshard is not None:
            fk_s = _screen_folds_nn_feat(
                self.fops, self.Xs, self.Y[a_idx],
                jnp.asarray(rem, X.dtype),
                jnp.asarray(self.lam_bar[act], X.dtype),
                self.lam_max_f[a_idx],
                jnp.asarray(self.Theta[act], X.dtype), self.n_bound[a_idx],
                jnp.asarray(self.Beta[act], X.dtype),
                jnp.asarray(self.fshard.shard_features(
                    self.Beta[act].astype(self.X_np.dtype))),
                jnp.asarray(self.fshard.shard_features(
                    self.Cprev[act].astype(self.X_np.dtype))),
                self.masks_d[a_idx], self.col_n_sf[:, a_idx], self.safety,
                screen=self.screen_mode)
            return self.fshard.unshard_features(np.asarray(fk_s))
        return _screen_folds_nn(
            X, self.Y[a_idx], jnp.asarray(rem, X.dtype),
            jnp.asarray(self.lam_bar[act], X.dtype), self.lam_max_f[a_idx],
            jnp.asarray(self.Theta[act], X.dtype), self.n_bound[a_idx],
            jnp.asarray(self.Beta[act], X.dtype),
            jnp.asarray(self.Cprev[act], X.dtype), self.masks_d[a_idx],
            self.col_n_f[a_idx], self.safety, screen=self.screen_mode,
            use_pallas=self.pallas)

    def make_launch(self, cohort) -> _Launch:
        with spans.span("fold.solve") as sp:
            N, p = self.N, self.p
            p_b = max(_feature_bucket(int(fkk[0].sum()), p, self.min_bucket,
                                      self.margin)
                      for _, fkk, _, _ in cohort)
            S_list = [_expand_set(fkk[0], fkk, p_b) for _, fkk, _, _ in cohort]
            for (k, _, _, _), S in zip(cohort, S_list):
                margin_fill_nn(S, self.Cprev[k], p_b)

            Ka = len(cohort)
            m_ks = [mk for _, _, mk, _ in cohort]
            len2 = _pow2_len(max(m_ks))
            X_subs = np.zeros((Ka, N, p_b), dtype=self.X_np.dtype)
            beta0s = np.zeros((Ka, p_b), dtype=self.X_np.dtype)
            lam_pads = np.zeros((Ka, len2))
            valids = np.zeros((Ka, len2), dtype=bool)
            col_idxs = []
            for t, ((k, _, mk, _), S) in enumerate(zip(cohort, S_list)):
                col_idx = np.nonzero(S)[0]
                X_subs[t, :, :len(col_idx)] = (self.X_np[:, col_idx]
                                               * self.masks_np[k][:, None])
                beta0s[t, :len(col_idx)] = self.Beta[k][col_idx]
                chunk = self.lambdas[self.j_pos[k]:self.j_pos[k] + mk]
                lam_pads[t, :mk] = chunk
                lam_pads[t, mk:] = chunk[-1]
                valids[t, :mk] = True
                col_idxs.append(col_idx)
            X = self.X
            X_subs_d = jnp.asarray(X_subs)
            L_subs = _spectral_norms_f(X_subs_d)
            key = ("nn-folds", Ka, N, p, str(X.dtype), self.max_iter,
                   self.check_every, self.mesh, p_b, len2, self.pallas,
                   "squared")
            if key not in self.seen_keys:
                self.seen_keys.add(key)
                self.stats.n_compilations += 1
            k_rows = jnp.asarray(np.asarray([k for k, _, _, _ in cohort]))
            runner = _fold_sweep("nn", self.mesh, Ka, self.max_iter,
                                 self.check_every, use_pallas=self.pallas)
            cols = np.stack([bucket_columns(c, p_b) for c in col_idxs])
            outputs = runner(
                X, X_subs_d, jnp.asarray(cols), self.Y[k_rows], L_subs,
                jnp.asarray(lam_pads, X.dtype), jnp.asarray(valids),
                jnp.asarray(beta0s), self.tol,
                jnp.asarray(self.gap_scales[[k for k, _, _, _ in cohort]],
                            X.dtype))
        self.solve_time += sp.seconds
        return _Launch(sweep=cohort, col_idxs=col_idxs, lam_pads=lam_pads,
                       outputs=outputs, p_b=p_b, g_b=0)


# ---------------------------------------------------------------------------
# Fold-batched SGL paths (the engine behind sgl_cv / stability_selection)
# ---------------------------------------------------------------------------

def sgl_fold_paths(X, y, spec: GroupSpec, alpha, masks, lambdas, *,
                   screen: str = "tlfre", tol=1e-9, max_iter: int = 20000,
                   safety: float = 0.0, specnorm_method: str = "power",
                   check_every: int = 10, min_bucket: int = 64,
                   min_group_bucket: int = 16, margin: float = 0.125,
                   chunk_init: int = 8, chunk_cap: int = 64,
                   schedule: str = "elastic", use_pallas=None, mesh=None,
                   mus=None, init=None, compile_keys=None,
                   feature_shards: int = 0, loss=SQUARED):
    """Solve the SAME lambda grid on K masked row-subsets of (X, y).

    ``masks``: (K, N) 0/1 — 1 marks rows in subset k's training problem.
    ``y`` is (N,) — one response shared by every subset — or (K, N) —
    per-fold responses on the full row index (stacked multi-job serving,
    per-fold-centered CV).  Returns ``(betas (K, J, p), kept (K, J),
    iters (K, J), stats, (screen_time, solve_time, setup_time))``.  Grid
    points at/above a fold's own lambda_max get exact zeros.

    ``schedule='elastic'`` (default) gives every fold its own speculative
    chunk length and dispatches cohorts of like-paced folds as independent
    asynchronous launches — a slow fold no longer gates the fast folds'
    chunks (``schedule='lockstep'`` restores the shared-chunk segment
    loop).  ``chunk_cap`` bounds any fold's chunk.  ``use_pallas`` (auto:
    float32 on TPU) routes the stacked grid screen through the fused
    fold-stack kernels and the sweep prox/certification through the f32
    kernels; float64 runs never engage them.

    ``mus`` (optional, (K, p)): per-fold train-row column means for
    leakage-free centering.  Fold k then solves on the centered design
    ``M_k (X - 1 mu_k^T)`` — threaded through the shared-X algebra as
    rank-one corrections (xty, column/spectral norms, screening GEMM,
    certification GEMV), so the stacked screens and the vmapped sweep
    survive centering with the ONE shared (N, p) design.  The caller
    supplies ``y`` rows already centered by the per-fold train means.

    ``init`` (optional ``FoldState``): exact warm state at a common
    reference lambda (``SGLSession.refine``) — the engine starts its
    screening/warm-start chain there instead of at each fold's lambda_max.
    ``compile_keys`` (optional set): persistent sweep-shape cache shared
    across calls, as in ``sgl_path_batched``.

    ``loss`` must support the masked-row embedding (``f(0, 0) == 0`` per
    sample); losses that don't (e.g. logistic) raise ``NotImplementedError``
    — solve per-fold single paths instead.
    """
    if screen not in ("tlfre", "gapsafe", "none"):
        raise ValueError(f"unknown screen mode {screen!r}")
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected one of "
                         f"{SCHEDULES}")
    loss = get_loss(loss)
    if not loss.supports_masked_rows:
        # the masked-row embedding needs f(0, 0) == 0 per sample so held-out
        # rows drop out of every inner product; the logistic NLL has
        # f(0, 0) = log 2, so fold batching would corrupt every certificate
        raise NotImplementedError(
            f"fold-batched paths require a loss whose masked rows vanish; "
            f"{loss.name!r} does not support the masked-row embedding")
    if int(feature_shards) > 1 and spec.feature_weights is not None:
        raise ValueError("feature_shards does not support adaptive feature "
                         "weights; drop one or the other")
    X = jnp.asarray(X)
    N, p = X.shape
    G = spec.num_groups
    masks_np = np.asarray(masks, dtype=float)
    K = masks_np.shape[0]
    y_rows_np = np.asarray(y, dtype=float)
    if y_rows_np.ndim == 1:
        y_rows_np = np.broadcast_to(y_rows_np, (K, N))
    lambdas = np.asarray(lambdas, dtype=float)
    J = len(lambdas)
    centered = mus is not None
    # the fused f32 kernels assume unit l1 thresholds; adaptive feature
    # weights fall back to the jnp route (same gate as the path engine)
    pallas = (_pallas_active(use_pallas, X.dtype)
              and spec.feature_weights is None)

    # ---- per-fold geometry, batched into a handful of GEMMs ---------------
    with spans.span("fold.setup") as sp:
        masks_d = jnp.asarray(masks_np, X.dtype)
        Y = masks_d * jnp.asarray(y_rows_np, X.dtype)             # (K, N)
        col2_f = mm(masks_d, X * X)                                # (K, p)
        if centered:
            mus_d = jnp.asarray(mus, X.dtype)
            # centered correlations / norms via rank-one corrections:
            # (X - 1 mu^T)^T v = X^T v - mu (1^T v);
            # sum m (x-mu)^2 = col2 - n mu^2
            xty_f = mm(Y, X) - jnp.sum(Y, axis=1)[:, None] * mus_d
            n_train = jnp.sum(masks_d, axis=1)
            col2_f = jnp.maximum(col2_f - n_train[:, None] * mus_d ** 2, 0.0)
        else:
            mus_d = None
            xty_f = mm(Y, X)                                      # (K, p)
        lam_max_f, g_star_f = jax.vmap(
            lambda c: lambda_max_sgl(spec, c, alpha))(xty_f)
        col_n_f = jnp.sqrt(col2_f)
        if specnorm_method == "power":
            # one fold at a time: peak memory stays (N, p), not (K, N, p) —
            # group_spectral_norms is jitted once and reused across folds
            gspec_f = jnp.stack([
                group_spectral_norms(
                    masks_d[k][:, None] * (X - mus_d[k][None, :] if centered
                                           else X), spec)
                for k in range(K)])
        else:
            gspec_f = jnp.sqrt(jax.vmap(lambda c2: jax.ops.segment_sum(
                c2, spec.group_ids, num_segments=G))(col2_f))
        # boundary normal of Theorem 12 at each fold's own lambda_max, masked
        lam_max_np = np.asarray(lam_max_f, dtype=float)
        lam_max_div = jnp.asarray(np.where(lam_max_np > 0, lam_max_np, 1.0),
                                  X.dtype)
        W = shrink(xty_f / lam_max_div[:, None])
        w_star = jnp.where(spec.group_ids[None, :] == g_star_f[:, None], W,
                           0.0)
        n_bound = mm(w_star, X.T)                                 # (K, N)
        if centered:
            n_bound = n_bound - jnp.sum(w_star * mus_d, axis=1)[:, None]
        n_bound = masks_d * n_bound
        jax.block_until_ready((col_n_f, gspec_f, n_bound))
        # feature sharding covers the STACKED GRID SCREENS only; the per-fold
        # stats above and the bucketed sweeps keep the full-X algebra, so the
        # sharded fold route certifies against the identical reference numbers
        fshard = None
        if int(feature_shards) > 1:
            from ..distributed.feature_shard import plan_feature_shards
            fshard = plan_feature_shards(int(feature_shards), p, spec)
            if fshard.n_shards <= 1:
                fshard = None
    setup_time = sp.seconds

    stats = EngineStats()
    seen_keys = compile_keys if compile_keys is not None else set()
    eng = _SGLFoldEngine(
        X, masks_np, y_rows_np, lambdas, lam_max_np, np.asarray(xty_f),
        tol=tol, max_iter=max_iter, safety=safety, check_every=check_every,
        min_bucket=min_bucket, margin=margin, mesh=mesh, pallas=pallas,
        screen_mode=screen, stats=stats, seen_keys=seen_keys,
        spec=spec, alpha=alpha, Y=Y, masks_d=masks_d, col_n_f=col_n_f,
        gspec_f=gspec_f, lam_max_f=lam_max_f, n_bound=n_bound, mus_d=mus_d,
        mus_np=np.asarray(mus, dtype=float) if centered else None,
        min_group_bucket=min_group_bucket, fshard=fshard, loss=loss)
    if init is not None:
        eng.load_init(init)
    for k in range(K):
        while (eng.j_pos[k] < J
               and lambdas[eng.j_pos[k]] >= lam_max_np[k] * (1.0 - 1e-12)):
            eng.j_pos[k] += 1                # beta* = 0 at/above fold lam_max
    eng.run(schedule, chunk_init, chunk_cap)

    return eng.betas_out, eng.kept_out, eng.iters_out, stats, (
        eng.screen_time, eng.solve_time, setup_time)


# ---------------------------------------------------------------------------
# Fold-batched nonnegative-Lasso paths
# ---------------------------------------------------------------------------

def nn_fold_paths(X, y, masks, lambdas, *, screen: str = "dpc", tol=1e-9,
                  max_iter: int = 20000, safety: float = 0.0,
                  check_every: int = 10, min_bucket: int = 64,
                  margin: float = 0.125, chunk_init: int = 8,
                  chunk_cap: int = 64, schedule: str = "elastic",
                  use_pallas=None, mesh=None, init=None, compile_keys=None,
                  feature_shards: int = 0):
    """Nonnegative-Lasso analogue of ``sgl_fold_paths`` (DPC / Gap-Safe).

    ``y`` is (N,) or per-fold (K, N) rows; ``schedule`` / ``chunk_cap`` /
    ``use_pallas`` / ``init`` / ``compile_keys`` as in ``sgl_fold_paths``
    (no centering — it breaks the nonnegativity geometry).  A fold whose
    ``max_i <x_i, y>`` is nonpositive has the all-zero path and simply
    drops out (the single-path driver raises instead)."""
    if screen not in ("dpc", "gapsafe", "none"):
        raise ValueError(f"unknown screen mode {screen!r}")
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule {schedule!r}; expected one of "
                         f"{SCHEDULES}")
    X = jnp.asarray(X)
    N, p = X.shape
    masks_np = np.asarray(masks, dtype=float)
    K = masks_np.shape[0]
    y_rows_np = np.asarray(y, dtype=float)
    if y_rows_np.ndim == 1:
        y_rows_np = np.broadcast_to(y_rows_np, (K, N))
    lambdas = np.asarray(lambdas, dtype=float)
    J = len(lambdas)
    pallas = _pallas_active(use_pallas, X.dtype)

    with spans.span("fold.setup") as sp:
        masks_d = jnp.asarray(masks_np, X.dtype)
        Y = masks_d * jnp.asarray(y_rows_np, X.dtype)
        xty_f = mm(Y, X)
        lam_max_f, i_star_f = jax.vmap(lambda_max_nn)(xty_f)
        col_n_f = jnp.sqrt(mm(masks_d, X * X))
        lam_max_np = np.asarray(lam_max_f, dtype=float)
        n_bound = masks_d * X[:, np.asarray(i_star_f)].T          # (K, N)
        jax.block_until_ready((col_n_f, n_bound))
        fshard = None
        if int(feature_shards) > 1:
            from ..distributed.feature_shard import plan_feature_shards
            fshard = plan_feature_shards(int(feature_shards), p, None)
            if fshard.n_shards <= 1:
                fshard = None
    setup_time = sp.seconds

    stats = EngineStats()
    seen_keys = compile_keys if compile_keys is not None else set()
    eng = _NNFoldEngine(
        X, masks_np, y_rows_np, lambdas, lam_max_np, np.asarray(xty_f),
        tol=tol, max_iter=max_iter, safety=safety, check_every=check_every,
        min_bucket=min_bucket, margin=margin, mesh=mesh, pallas=pallas,
        screen_mode=screen, stats=stats, seen_keys=seen_keys,
        Y=Y, masks_d=masks_d, col_n_f=col_n_f, lam_max_f=lam_max_f,
        n_bound=n_bound, fshard=fshard)
    if init is not None:
        eng.load_init(init)
    for k in range(K):
        if lam_max_np[k] <= 0:
            eng.j_pos[k] = J                   # all-zero path for this fold
            continue
        while (eng.j_pos[k] < J
               and lambdas[eng.j_pos[k]] >= lam_max_np[k] * (1.0 - 1e-12)):
            eng.j_pos[k] += 1
    eng.run(schedule, chunk_init, chunk_cap)

    return eng.betas_out, eng.kept_out, eng.iters_out, stats, (
        eng.screen_time, eng.solve_time, setup_time)


# ---------------------------------------------------------------------------
# K-fold cross-validation
# ---------------------------------------------------------------------------

def _cv_statistics(X_np, y_np, folds, lambdas, betas, lam_max, kept, stats,
                   times, iters=None, mus=None, y_means=None):
    """Held-out MSE / selection statistics from per-fold grid solutions.

    ``mus`` / ``y_means`` (per-fold centering): fold k's betas solve the
    centered training problem, so its held-out prediction is
    ``X beta - mu_k . beta + ybar_k``."""
    K = len(folds)
    J = len(lambdas)
    mse = np.zeros((K, J))
    for k, (_, val) in enumerate(folds):
        pred = betas[k] @ X_np[val].T                            # (J, |val|)
        if mus is not None:
            pred = pred - (betas[k] @ mus[k])[:, None] + y_means[k]
        err = y_np[val][None, :] - pred
        mse[k] = np.mean(err * err, axis=1)
    mean_mse = mse.mean(axis=0)
    se_mse = mse.std(axis=0, ddof=1) / np.sqrt(K) if K > 1 else \
        np.zeros(J)
    best = int(np.argmin(mean_mse))
    # 1-SE rule: sparsest (largest-lambda) model within one SE of the best
    within = np.nonzero(mean_mse <= mean_mse[best] + se_mse[best])[0]
    idx_1se = int(within[np.argmax(lambdas[within])])
    return CVResult(
        lambdas=lambdas, fold_betas=betas, mse_path=mse, mean_mse=mean_mse,
        se_mse=se_mse, best_index=best, best_lambda=float(lambdas[best]),
        index_1se=idx_1se, lambda_1se=float(lambdas[idx_1se]), folds=folds,
        lam_max=lam_max, kept_features=kept, stats=stats,
        screen_time=times[0], solve_time=times[1], setup_time=times[2],
        fold_iters=iters)


def sgl_cv(X, y, spec: GroupSpec, alpha, *, n_folds: int = 5, folds=None,
           lambdas=None, n_lambdas: int = 100, min_ratio: float = 0.01,
           screen: str = "tlfre", tol=1e-9, max_iter: int = 20000,
           safety: float = 0.0, specnorm_method: str = "power",
           check_every: int = 10, seed: int = 0, mesh=None,
           min_bucket: int = 64, min_group_bucket: int = 16,
           margin: float = 0.125, chunk_init: int = 8,
           center: str = "global") -> CVResult:
    """K-fold cross-validation for SGL over a shared lambda grid.

    Legacy entry point, kept as a thin (bit-identical) shim over the
    declarative API: builds a one-shot ``Problem``/``Plan`` and runs
    ``SGLSession.cv`` — a persistent session additionally reuses compiled
    buckets and feeds ``session.refine``.

    All folds solve the SAME grid (anchored at the full-data lambda_max so
    held-out errors are comparable per grid point) with the fold-batched
    engine: one stacked screening GEMM per scheduler step and one vmapped /
    mesh-sharded sweep per cohort launch.  Per-fold solutions carry the
    same full-problem duality-gap certificates as the single-fold engine,
    so they match independent per-fold ``sgl_path`` runs to solver
    precision.  ``folds`` overrides the deterministic ``kfold_indices``
    split; ``mesh`` (from ``launch.mesh.make_fold_mesh``) shards the fold
    axis; ``center='per-fold'`` scores leakage-free per-fold-centered
    models.
    """
    from .problem import Plan, Problem, warn_legacy_entry_point
    from .session import SGLSession
    warn_legacy_entry_point("sgl_cv", "SGLSession.cv")
    plan = Plan(alpha=alpha, lambdas=lambdas, n_lambdas=n_lambdas,
                min_ratio=min_ratio, screen=screen, tol=tol,
                max_iter=max_iter, safety=safety,
                specnorm_method=specnorm_method, check_every=check_every,
                min_bucket=min_bucket, min_group_bucket=min_group_bucket,
                margin=margin, chunk_init=chunk_init, n_folds=n_folds,
                folds=folds, seed=seed, center=center, mesh=mesh)
    return SGLSession(Problem.sgl(X, y, spec)).cv(plan)


def nn_lasso_cv(X, y, *, n_folds: int = 5, folds=None, lambdas=None,
                n_lambdas: int = 100, min_ratio: float = 0.01,
                screen: str = "dpc", tol=1e-9, max_iter: int = 20000,
                safety: float = 0.0, check_every: int = 10, seed: int = 0,
                mesh=None, min_bucket: int = 64, margin: float = 0.125,
                chunk_init: int = 8) -> CVResult:
    """K-fold cross-validation for the nonnegative Lasso (DPC screening).

    Legacy shim over ``SGLSession.cv`` (see ``sgl_cv``)."""
    from .problem import Plan, Problem, warn_legacy_entry_point
    from .session import SGLSession
    warn_legacy_entry_point("nn_lasso_cv", "SGLSession.cv")
    plan = Plan(lambdas=lambdas, n_lambdas=n_lambdas, min_ratio=min_ratio,
                screen=screen, tol=tol, max_iter=max_iter, safety=safety,
                check_every=check_every, min_bucket=min_bucket,
                margin=margin, chunk_init=chunk_init, n_folds=n_folds,
                folds=folds, seed=seed, mesh=mesh)
    return SGLSession(Problem.nn_lasso(X, y)).cv(plan)


# ---------------------------------------------------------------------------
# Stability selection (Meinshausen & Buhlmann, 2010)
# ---------------------------------------------------------------------------

def stability_selection(X, y, spec: GroupSpec, alpha, *,
                        n_subsamples: int = 50, frac: float = 0.5,
                        lambdas=None, n_lambdas: int = 30,
                        min_ratio: float = 0.05, active_tol: float = 1e-8,
                        screen: str = "tlfre", tol=1e-7,
                        max_iter: int = 20000, safety: float = 0.0,
                        check_every: int = 10, seed: int = 0, mesh=None,
                        batch_size: int = 10,
                        specnorm_method: str = "fro") -> StabilityResult:
    """Selection probabilities over random row-subsamples, fold-batched.

    Legacy shim over ``SGLSession.stability``: runs the SGL grid on
    ``n_subsamples`` random ``frac``-subsamples (``batch_size`` at a time
    through the fold-batched engine) and reports the fraction of
    subsamples in which each feature is active at each lambda.
    ``specnorm_method`` defaults to the Frobenius bound: the per-subsample
    power iterations are the only setup cost that scales with B, and the
    bound only loosens screening, never correctness.
    """
    from .problem import Plan, Problem, warn_legacy_entry_point
    from .session import SGLSession
    warn_legacy_entry_point("stability_selection", "SGLSession.stability")
    plan = Plan(alpha=alpha, lambdas=lambdas, n_lambdas=n_lambdas,
                min_ratio=min_ratio, screen=screen, tol=tol,
                max_iter=max_iter, safety=safety,
                specnorm_method=specnorm_method, check_every=check_every,
                seed=seed, mesh=mesh, n_subsamples=n_subsamples,
                subsample_frac=frac, active_tol=active_tol,
                batch_size=batch_size)
    return SGLSession(Problem.sgl(X, y, spec)).stability(plan)
