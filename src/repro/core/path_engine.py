"""Device-resident batched lambda-path engine (TLFre / Gap-Safe / DPC).

The legacy drivers in ``path.py`` sync to host after EVERY lambda: one
screening GEMV, one numpy submatrix rebuild, one solver dispatch per grid
point — O(L) host round-trips for an L-point path.  This engine restructures
the path into a handful of *segments*, each one device round-trip:

  1. **Grid screening.**  At each segment boundary the ENTIRE remaining
     lambda grid is screened in one shot: the Theorem-12 ball centers of all
     remaining grid points share ``theta_bar``, so the L screening GEMVs
     collapse into a single (L, N) x (N, p) GEMM
     (``tlfre_screen_grid`` / ``dpc_screen_grid``) — the MXU-shaped
     formulation.  ``screen='gapsafe'`` instead uses the dynamic Gap-Safe
     ball around the latest exact dual; its center is shared across the
     grid, so the GEMM collapses further to one GEMV.  Row 0 of the grid
     (the next lambda) is the *safe base set* of the segment.

  2. **Speculative bucketed sweep with in-scan certification.**  The ball
     is near-vacuous a few grid steps past its reference, so distant rows
     of the grid screen cannot pick solver sets.  Instead the segment
     solves the next ``m`` lambdas on a fixed feature set S = safe base
     set + nearby-row union + a margin of top-ranked groups, padded to a
     power-of-two bucket (``GroupSpec.bucketed_subset``), inside ONE
     jitted ``lax.scan`` whose carry is the warm-started coefficient
     vector — the paper's exact-dual warm-start chain, kept on device.
     Solving on a superset of the true active set yields the true optimum,
     so each row certifies itself immediately after its solve: one full-X
     GEMV recovers the exact dual (Lemma-9 scaling) and the FULL-problem
     duality gap.  A failed certificate marks the scan dead — later rows
     skip via ``lax.cond`` instead of solving on a stale set — so at most
     one speculative solve per segment is wasted.

  3. **Single host sync.**  The host reads the per-row certificates once
     per segment, accepts the certified prefix (row 0 is solved on a
     provably safe superset, so progress is guaranteed), and seeds the next
     segment's screening and margin ranking with the last accepted row's
     exact dual — which the sweep already computed.

  4. **Pallas wiring.**  With ``use_pallas`` (auto: float32 on TPU), the
     screening reductions run through the fused ``screen_norms`` kernel,
     the FISTA prox through ``sgl_prox_padded``, and the certification
     GEMV through ``xtv`` — all via ``kernels.ops``, which interprets the
     kernels off-TPU.  The kernels are float32, so the engine only engages
     them for float32 problems (float64 exactness runs keep pure jnp).

Solver compilations are keyed on (feature bucket, group bucket, padded
width, pow2 chunk length) and reused across segments — O(log p) distinct
keys per path (``EngineStats.n_compilations``), versus one dispatch per
lambda for the legacy driver.

Knobs: ``min_bucket`` / ``min_group_bucket`` (smallest buckets, defaults
64 / 16), ``margin`` (bucket slack filled with top-ranked groups: the
bucket is the next power of two with at least ``margin`` fractional
headroom over the safe base set, default 0.125), ``chunk_init`` (initial
speculative chunk length, default 8; doubles on fully-certified segments).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .dpc import (dpc_screen_grid, dpc_screen_grid_feat, dual_scaling_nn,
                  gap_safe_screen_grid_nn, gap_safe_screen_grid_nn_feat,
                  lambda_max_nn, normal_vector_nn)
from .estimation import normal_vector_sgl
from .fenchel import shrink, sgl_penalty, weighted_l1
from .groups import GroupSpec, group_norms, pad_slots
from .lambda_max import dual_scaling_sgl, lambda_max_sgl
from .linalg import (column_norms, group_frobenius_norms,
                     group_spectral_norms, mm, spectral_norm)
from .losses import SQUARED, Loss, get_loss
from .path import PathResult, _bucket, default_lambda_grid
from .screening import (gap_safe_grid_radii, gap_safe_grid_radii_loss,
                        gap_safe_screen_grid, gap_safe_screen_grid_feat,
                        tlfre_screen_grid, tlfre_screen_grid_feat)
from .solver import fista_nn_lasso, fista_sgl


@dataclasses.dataclass
class EngineStats:
    """Host-interaction accounting for the batched engine.

    ``n_segments`` counts sweep round-trips (the legacy driver makes one
    round-trip per lambda).  ``n_compilations`` counts distinct sweep
    shapes — actual solver compilations; the O(log p) claim is about this
    number.  ``n_rejected`` counts speculative rows whose certificate
    failed (at most one solved row per segment is wasted; the rest are
    skipped on device).  ``n_uncertified`` counts rows returned WITHOUT a
    full-problem certificate: a segment's row 0 whose gap stayed above
    ``tol`` (its solve hit ``max_iter``); the engine keeps the best iterate
    so the path can go on, and the row is not certified.
    ``n_pallas_screens`` counts grid screens that ran
    through the fused Pallas kernels (always 0 on float64 paths — the
    kernels are float32 and ``_pallas_active`` never engages them there).
    ``fold_sweeps`` (fold drivers only) is a per-fold count of sweep
    launches the fold participated in — under elastic scheduling fast
    folds stop paying launches gated by slow folds, so their counts drop
    below the lockstep numbers."""
    n_segments: int = 0
    n_screens: int = 0
    n_compilations: int = 0
    n_rejected: int = 0
    n_uncertified: int = 0
    n_pallas_screens: int = 0
    buckets: list = dataclasses.field(default_factory=list)  # (p_b, g_b, m, k)
    fold_sweeps: object = None   # (K,) launch counts from the last fold run

    def merge(self, other: "EngineStats", *, buckets: bool = True) -> None:
        """Accumulate another run's counters into this one (session /
        server aggregation).  ``buckets=False`` keeps the bucket log out of
        aggregates where per-run bucket tuples would be meaningless.
        ``fold_sweeps`` is per-run (fold identity differs across runs), so
        aggregates never accumulate it."""
        self.n_segments += other.n_segments
        self.n_screens += other.n_screens
        self.n_compilations += other.n_compilations
        self.n_rejected += other.n_rejected
        self.n_uncertified += other.n_uncertified
        self.n_pallas_screens += other.n_pallas_screens
        if buckets:
            self.buckets.extend(other.buckets)


def _pallas_active(use_pallas: Optional[bool], dtype) -> bool:
    """The Pallas kernels are float32; never engage them for float64 runs."""
    if dtype != jnp.float32:
        return False
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return bool(use_pallas)


def _xtv(X, v, use_pallas: bool):
    if use_pallas:
        from ..kernels import ops as _kops
        return _kops.xtv(X, v)
    return mm(X.T, v)


def _padded_prox(spec: GroupSpec):
    """Fused SGL prox through the Pallas kernel on the slot-major layout.

    Padding columns beyond the garbage bin's first ``n_max`` slots never
    enter the padded view; their gradient is zero and they start at zero, so
    scattering back onto a zero vector is exact."""
    from ..kernels import ops as _kops
    idx, mask = spec.pad_index.T, spec.pad_mask.T

    def prox(v, t_l1, t_group):
        out = _kops.sgl_prox_padded(pad_slots(spec, v.astype(jnp.float32)),
                                    mask, t_l1, t_group)
        return jnp.zeros_like(v).at[idx].add(
            jnp.where(mask, out, 0.0).astype(v.dtype))

    return prox


def _pow2_len(m: int) -> int:
    b = 1
    while b < m:
        b *= 2
    return b


# The remaining-grid length shrinks every segment; pad it to a power of two
# (repeating the last lambda) so the jitted grid screens retrace O(log L)
# times per path instead of once per segment.
_tlfre_grid_jit = functools.partial(jax.jit, static_argnames=("use_pallas",))(
    tlfre_screen_grid)
_gap_safe_grid_jit = functools.partial(
    jax.jit, static_argnames=("use_pallas",))(gap_safe_screen_grid)
_gap_safe_radii_jit = jax.jit(gap_safe_grid_radii)
# loss-generic radii: the Loss singleton is hashable, so it rides as a
# static positional (one retrace per loss, not per call)
_gap_safe_radii_loss_jit = functools.partial(
    jax.jit, static_argnums=(0,))(gap_safe_grid_radii_loss)
_dpc_grid_jit = functools.partial(jax.jit, static_argnames=("use_pallas",))(
    dpc_screen_grid)
_gap_safe_nn_jit = jax.jit(gap_safe_screen_grid_nn)

# Feature-sharded grid screens: the executor (``FeatureOps``) is static —
# it decides vmap-vs-shard_map at trace time — everything else is traced.
_tlfre_feat_jit = functools.partial(jax.jit, static_argnums=(0,))(
    tlfre_screen_grid_feat)
_gap_safe_feat_jit = functools.partial(jax.jit, static_argnums=(0,))(
    gap_safe_screen_grid_feat)
_dpc_feat_jit = functools.partial(jax.jit, static_argnums=(0,))(
    dpc_screen_grid_feat)
_gap_safe_nn_feat_jit = functools.partial(jax.jit, static_argnums=(0,))(
    gap_safe_screen_grid_nn_feat)


def _pad_grid(lambdas_rem: np.ndarray, dtype):
    """(padded device grid, real length) with the tail repeating the last
    lambda — extra rows are computed and discarded on the host slice."""
    L = len(lambdas_rem)
    Lp = _pow2_len(L)
    pad = np.concatenate([lambdas_rem, np.full(Lp - L, lambdas_rem[-1])])
    return jnp.asarray(pad, dtype), L


def _feature_bucket(n_base: int, p: int, min_bucket: int,
                    margin: float) -> int:
    """Next power-of-two bucket with at least ``margin`` fractional slack
    over the safe base set (the slack is filled with speculative groups)."""
    b = min(_bucket(max(n_base, 1), min_bucket), p)
    if b < p and b - n_base < margin * b:
        b = min(b * 2, p)
    return b


def _expand_set(base, fk_np, cap: int):
    """Union nearby grid-screen rows into the base set while it stays under
    ``cap`` features — free lookahead from the one-shot grid screen."""
    S = base.copy()
    for r in range(1, min(len(fk_np), 8)):
        trial = S | fk_np[r]
        if int(trial.sum()) > cap:
            break
        S = trial
    return S


def margin_fill_sgl(S, c_prev_np, gid, sizes_np, weights_np, p_b: int,
                    g_b: int, feature_weights_np=None):
    """Fill spare bucket capacity with whole groups ranked by their dual
    correlation (Lemma-9 margin at the latest exact dual ``c_prev``).

    Shared by the single-fold engine and the fold-batched CV drivers so the
    speculative-set rule cannot drift between them.  Mutates ``S``.  With
    adaptive l1 weights the shrinkage threshold is per-feature."""
    if S.all():
        return
    G = len(sizes_np)
    thresh = 1.0 if feature_weights_np is None else feature_weights_np
    shr = np.sign(c_prev_np) * np.maximum(np.abs(c_prev_np) - thresh, 0.0)
    score = np.sqrt(np.bincount(gid, weights=shr * shr,
                                minlength=G)) / weights_np
    g_S = np.unique(gid[S])
    in_S = np.zeros(G, dtype=bool)
    in_S[g_S] = True
    n_S, n_grp = int(S.sum()), len(g_S)
    for g in np.argsort(-score):
        if in_S[g]:
            continue
        if n_grp + 1 >= g_b or n_S + int(sizes_np[g]) > p_b:
            continue
        S[gid == g] = True
        in_S[g] = True
        n_S += int(sizes_np[g])
        n_grp += 1


def margin_fill_nn(S, c_prev_np, p_b: int):
    """Fill spare capacity with the top features by dual correlation
    (nonnegative-Lasso analogue of ``margin_fill_sgl``).  Mutates ``S``."""
    spare = p_b - int(S.sum())
    if spare > 0 and not S.all():
        cand = np.asarray(c_prev_np, dtype=float).copy()
        cand[S] = -np.inf
        S[np.argpartition(-cand, spare - 1)[:spare]] = True


# ---------------------------------------------------------------------------
# Jitted sweeps: lax.scan over a lambda chunk, carry = (beta, alive).
# Each row certifies itself against the FULL problem right after its solve;
# a failed certificate kills the remaining rows on device.
# ---------------------------------------------------------------------------

def sweep_sgl_core(X, X_sub, y, spec: GroupSpec, sub_spec: GroupSpec, alpha,
                   lipschitz, lams, valid, beta0, tol, gap_scale, mu=None, *,
                   max_iter: int, check_every: int, use_pallas: bool,
                   loss: Loss = SQUARED):
    """``mu`` (optional, (p,)): per-fold column means for leakage-free
    centering — the certification GEMV runs against the SHARED design, so
    the centered full-problem correlation is the rank-one correction
    ``X^T rho - mu * sum(rho)`` (``X_sub`` is already materialized
    centered+masked by the caller).  ``mu=None`` keeps the exact
    uncentered graph.  ``loss`` (static) swaps the smooth data-fit term in
    both the inner solver and the full-problem certificate; the squared
    singleton emits the historical graph bit-for-bit."""
    prox = _padded_prox(sub_spec) if use_pallas else None
    N = y.shape[0]
    p = X.shape[1]
    tol = loss.effective_tol(tol, y.dtype)

    def step(carry, xs):
        beta, alive = carry
        lam, ok = xs

        def run(b):
            res = fista_sgl(X_sub, y, sub_spec, lam, alpha, lipschitz, b,
                            max_iter=max_iter, check_every=check_every,
                            tol=tol, prox=prox, loss=loss)
            fit = mm(X_sub, res.beta)
            resid = loss.residual(y, fit)
            rho = resid / lam
            c = _xtv(X, rho, use_pallas).astype(b.dtype)   # full-X GEMV
            if mu is not None:
                c = c - (mu * jnp.sum(rho)).astype(b.dtype)
            s = dual_scaling_sgl(spec, c, alpha)
            theta = (s * rho).astype(b.dtype)
            pen = sgl_penalty(sub_spec, res.beta, alpha)
            pval = loss.primal_value(y, fit, resid) + lam * pen
            dval = loss.dual_value(y, theta, lam)
            gap = pval - dval
            good = gap <= tol * gap_scale * 1.01
            return res.beta, theta, (s * c).astype(b.dtype), good, res.iters

        def skip(b):
            return (b, jnp.zeros(N, b.dtype), jnp.zeros(p, b.dtype),
                    jnp.asarray(False), jnp.asarray(0))

        beta_new, theta, ctheta, good, its = jax.lax.cond(
            alive & ok, run, skip, beta)
        return (beta_new, alive & good), (beta_new, theta, ctheta, good, its)

    _, out = jax.lax.scan(step, (beta0, jnp.asarray(True)),
                          (lams, valid))
    return out   # (betas, thetas, cthetas, good, iters)


_sweep_sgl = functools.partial(
    jax.jit,
    static_argnames=("max_iter", "check_every", "use_pallas", "loss"))(
        sweep_sgl_core)


def sweep_nn_core(X, X_sub, y, lipschitz, lams, valid, beta0, tol,
                  gap_scale, *, max_iter: int, check_every: int,
                  use_pallas: bool):
    N = y.shape[0]
    p = X.shape[1]
    tol = SQUARED.effective_tol(tol, y.dtype)

    def step(carry, xs):
        beta, alive = carry
        lam, ok = xs

        def run(b):
            res = fista_nn_lasso(X_sub, y, lam, lipschitz, b,
                                 max_iter=max_iter, check_every=check_every,
                                 tol=tol)
            resid = y - mm(X_sub, res.beta)
            rho = resid / lam
            c = _xtv(X, rho, use_pallas).astype(b.dtype)
            s = dual_scaling_nn(c)
            theta = (s * rho).astype(b.dtype)
            pval = 0.5 * jnp.vdot(resid, resid) + lam * jnp.sum(res.beta)
            d = y - lam * theta
            dval = 0.5 * jnp.vdot(y, y) - 0.5 * jnp.vdot(d, d)
            gap = pval - dval
            good = gap <= tol * gap_scale * 1.01
            return res.beta, theta, (s * c).astype(b.dtype), good, res.iters

        def skip(b):
            return (b, jnp.zeros(N, b.dtype), jnp.zeros(p, b.dtype),
                    jnp.asarray(False), jnp.asarray(0))

        beta_new, theta, ctheta, good, its = jax.lax.cond(
            alive & ok, run, skip, beta)
        return (beta_new, alive & good), (beta_new, theta, ctheta, good, its)

    _, out = jax.lax.scan(step, (beta0, jnp.asarray(True)),
                          (lams, valid))
    return out


_sweep_nn = functools.partial(
    jax.jit, static_argnames=("max_iter", "check_every", "use_pallas"))(
        sweep_nn_core)


# ---------------------------------------------------------------------------
# Feature-sharded sweeps.  The solve bucket stays single-device (surviving
# columns are gathered host-side exactly as in the unsharded engine), but the
# in-scan FULL-problem certification runs feature-parallel: the cert GEMV is
# a per-shard partial ``X_b^T rho`` and the Lemma-9 scaling reduces shard
# maxima/minima — both exactly associative, so kept-sets and accepted betas
# match the unsharded engine bitwise (f64).  ``c_theta`` stays in the stacked
# (S, p_shard) layout across segments; only the host margin ranking sees the
# unsharded view.  No mu support: fold sweeps keep full-X certification.
# ---------------------------------------------------------------------------

def sweep_sgl_core_feat(Xs, X_sub, y, specs, sub_spec: GroupSpec, alpha,
                        lipschitz, lams, valid, beta0, tol, gap_scale, *,
                        ops, max_iter: int, check_every: int):
    from ..distributed.feature_shard import cert_sgl
    N = y.shape[0]
    S_n, _, p_sh = Xs.shape
    tol = SQUARED.effective_tol(tol, y.dtype)

    def step(carry, xs):
        beta, alive = carry
        lam, ok = xs

        def run(b):
            res = fista_sgl(X_sub, y, sub_spec, lam, alpha, lipschitz, b,
                            max_iter=max_iter, check_every=check_every,
                            tol=tol, prox=None)
            resid = y - mm(X_sub, res.beta)
            rho = resid / lam
            c_s, s = cert_sgl(ops, Xs, specs, rho, alpha)
            c_s = c_s.astype(b.dtype)
            theta = (s * rho).astype(b.dtype)
            pen = (alpha * jnp.sum(sub_spec.weights.astype(b.dtype)
                                   * group_norms(sub_spec, res.beta))
                   + jnp.sum(jnp.abs(res.beta)))
            pval = 0.5 * jnp.vdot(resid, resid) + lam * pen
            d = y - lam * theta
            dval = 0.5 * jnp.vdot(y, y) - 0.5 * jnp.vdot(d, d)
            gap = pval - dval
            good = gap <= tol * gap_scale * 1.01
            return (res.beta, theta, (s * c_s).astype(b.dtype), good,
                    res.iters)

        def skip(b):
            return (b, jnp.zeros(N, b.dtype),
                    jnp.zeros((S_n, p_sh), b.dtype),
                    jnp.asarray(False), jnp.asarray(0))

        beta_new, theta, ctheta, good, its = jax.lax.cond(
            alive & ok, run, skip, beta)
        return (beta_new, alive & good), (beta_new, theta, ctheta, good, its)

    _, out = jax.lax.scan(step, (beta0, jnp.asarray(True)),
                          (lams, valid))
    return out   # (betas, thetas, cthetas (m, S, p_shard), good, iters)


def sweep_nn_core_feat(Xs, X_sub, y, lipschitz, lams, valid, beta0, tol,
                       gap_scale, *, ops, max_iter: int, check_every: int):
    from ..distributed.feature_shard import cert_nn
    N = y.shape[0]
    S_n, _, p_sh = Xs.shape
    tol = SQUARED.effective_tol(tol, y.dtype)

    def step(carry, xs):
        beta, alive = carry
        lam, ok = xs

        def run(b):
            res = fista_nn_lasso(X_sub, y, lam, lipschitz, b,
                                 max_iter=max_iter, check_every=check_every,
                                 tol=tol)
            resid = y - mm(X_sub, res.beta)
            rho = resid / lam
            c_s, s = cert_nn(ops, Xs, rho)
            c_s = c_s.astype(b.dtype)
            theta = (s * rho).astype(b.dtype)
            pval = 0.5 * jnp.vdot(resid, resid) + lam * jnp.sum(res.beta)
            d = y - lam * theta
            dval = 0.5 * jnp.vdot(y, y) - 0.5 * jnp.vdot(d, d)
            gap = pval - dval
            good = gap <= tol * gap_scale * 1.01
            return (res.beta, theta, (s * c_s).astype(b.dtype), good,
                    res.iters)

        def skip(b):
            return (b, jnp.zeros(N, b.dtype),
                    jnp.zeros((S_n, p_sh), b.dtype),
                    jnp.asarray(False), jnp.asarray(0))

        beta_new, theta, ctheta, good, its = jax.lax.cond(
            alive & ok, run, skip, beta)
        return (beta_new, alive & good), (beta_new, theta, ctheta, good, its)

    _, out = jax.lax.scan(step, (beta0, jnp.asarray(True)),
                          (lams, valid))
    return out


# jit cache for the sharded sweeps: ``ops`` (executor + mesh) is baked in
# via partial — FeatureOps is a hashable frozen dataclass, so the same
# (executor, iteration-budget) pair reuses one jitted callable process-wide.
_FEAT_SWEEPS: dict = {}


def _feat_sweep(kind: str, ops, max_iter: int, check_every: int):
    key = (kind, ops, max_iter, check_every)
    fn = _FEAT_SWEEPS.get(key)
    if fn is None:
        core = sweep_sgl_core_feat if kind == "sgl" else sweep_nn_core_feat
        fn = jax.jit(functools.partial(core, ops=ops, max_iter=max_iter,
                                       check_every=check_every))
        _FEAT_SWEEPS[key] = fn
    return fn


# ---------------------------------------------------------------------------
# SGL
# ---------------------------------------------------------------------------

def sgl_path_batched(X, y, spec: GroupSpec, alpha, *, lambdas=None,
                     n_lambdas: int = 100, min_ratio: float = 0.01,
                     screen: str = "tlfre", tol=1e-9, max_iter: int = 20000,
                     safety: float = 0.0, specnorm_method: str = "power",
                     check_every: int = 10, use_pallas: Optional[bool] = None,
                     min_bucket: int = 64, min_group_bucket: int = 16,
                     margin: float = 0.125, chunk_init: int = 8,
                     feature_shards: int = 0,
                     compile_keys: Optional[set] = None,
                     loss=SQUARED) -> PathResult:
    """Batched SGL path: grid screening, speculative bucketed sweeps with
    in-scan certification.

    Semantics match ``sgl_path``: same grid protocol, same exact-dual warm
    starts, and every accepted solution carries a full-problem duality-gap
    certificate at the solver tolerance, so the betas agree with the legacy
    driver to solver precision.

    ``feature_shards > 1`` runs the screening GEMMs, group-stat reductions
    and in-scan certification feature-parallel over a group-aligned column
    partition (``distributed.feature_shard``; shard_map on a 'feature' mesh
    when the host has the devices, stacked-vmap otherwise).  Kept-group
    sets and accepted betas match the unsharded engine — bitwise in f64 —
    because every cross-shard reduction (min of shrink roots, max of
    correlations) is exactly associative; the solve bucket itself stays
    single-device.  The shard count degrades to the largest divisor of the
    group count (``effective_shards``); pallas kernels never engage on the
    sharded route.

    ``compile_keys`` is an optional persistent set of sweep-shape keys
    (owned by ``SGLSession``): jax's jit cache is process-global, so a
    shape seen in ANY earlier call never recompiles — threading one set
    across calls makes ``EngineStats.n_compilations`` count compilations
    actually paid, not shapes per call.

    ``loss`` (a ``core.losses`` singleton or name) swaps the smooth
    data-fit term.  Non-squared losses screen with Gap-Safe balls only
    (TLFre's Theorem-12 ball is squared-loss algebra) and run the pure-jnp
    route (no Pallas kernels, no feature shards).
    """
    if screen not in ("tlfre", "gapsafe", "none"):
        raise ValueError(f"unknown screen mode {screen!r}")
    loss = get_loss(loss)
    squared = loss.name == "squared"
    if not squared and screen == "tlfre":
        raise ValueError(
            f"screen='tlfre' requires squared loss (Theorem 12 is "
            f"squared-loss algebra); use screen='gapsafe' for {loss.name}")
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    N, p = X.shape
    G = spec.num_groups

    fshard = None
    if feature_shards and int(feature_shards) > 1:
        if not squared or spec.feature_weights is not None:
            raise ValueError(
                "feature_shards requires squared loss and no adaptive "
                "feature weights (the sharded cert/spec stacking does not "
                "carry them)")
        from ..distributed import feature_shard as _fs
        plan_fs = _fs.plan_feature_shards(int(feature_shards), p, spec)
        if plan_fs.n_shards > 1:
            fshard = plan_fs
    pallas = (_pallas_active(use_pallas, X.dtype) and fshard is None
              and squared and spec.feature_weights is None)

    t0 = time.perf_counter()
    if fshard is not None:
        fmesh = _fs.resolve_feature_mesh(fshard.n_shards)
        fops = _fs.feature_ops(fshard.n_shards, fmesh)
        Xs = fops.place(fshard.stack_columns(np.asarray(X)))
        specs_s = fshard.specs_stacked
        xty_s = _fs.sharded_xtv(fops, Xs, y)
        xty_np = fshard.unshard_features(np.asarray(xty_s))
        xty = jnp.asarray(xty_np)
        lam_max, g_star = lambda_max_sgl(spec, xty, alpha)
        lam_max = float(lam_max)
        col_n_s = _fs.sharded_column_norms(fops, Xs)
        if specnorm_method == "power":
            gspec_s = _fs.sharded_group_spectral_norms(fops, Xs, specs_s)
        else:
            gspec_s = _fs.sharded_group_frobenius_norms(fops, Xs, specs_s)
        # Theorem-15 boundary normal X w*, feature-parallel: w* is supported
        # on the argmax group only, so X w* is a partial-GEMV psum
        w_s = shrink(_fs.sharded_xtv(fops, Xs, y / lam_max))
        gid_stack = jnp.asarray(fshard.shard_features(
            np.asarray(spec.group_ids) + 1) - 1)            # pads -> -1
        n_boundary = _fs.sharded_fit(
            fops, Xs, jnp.where(gid_stack == g_star, w_s, 0.0))
        L_full = None          # only the full-bucket fallback needs it
        r0 = y                 # sharded route is squared-loss only
        jax.block_until_ready((col_n_s, gspec_s, n_boundary))
    else:
        # -grad of the loss at beta = 0; y itself for squared loss, so the
        # squared setup GEMV X.T @ y is unchanged
        r0 = loss.residual_at_zero(y)
        xty = mm(X.T, r0)
        lam_max, g_star = lambda_max_sgl(spec, xty, alpha)
        lam_max = float(lam_max)
        col_n = column_norms(X)
        if specnorm_method == "power":
            gspec = group_spectral_norms(X, spec)
        else:
            gspec = group_frobenius_norms(X, spec)
        L_full = spectral_norm(X) ** 2
        jax.block_until_ready((col_n, gspec, L_full))
    setup_time = time.perf_counter() - t0

    if lambdas is None:
        lambdas = default_lambda_grid(lam_max, n_lambdas, min_ratio)
    lambdas = np.asarray(lambdas, dtype=float)
    J = len(lambdas)

    betas = np.zeros((J, p))
    iters = np.zeros(J, dtype=np.int64)
    kept_feat = np.zeros(J, dtype=np.int64)
    kept_grp = np.zeros(J, dtype=np.int64)
    stats = EngineStats()
    screen_time = 0.0
    solve_time = 0.0
    X_np = np.asarray(X)
    gid = np.asarray(spec.group_ids)
    sizes_np = np.asarray(spec.sizes)
    weights_np = np.asarray(spec.weights)
    fw_np = (None if spec.feature_weights is None
             else np.asarray(spec.feature_weights))
    gap_scale = loss.gap_scale_host(y)

    theta_bar = r0 / lam_max            # exact dual at lam_max (Thm 8)
    if fshard is not None:
        c_prev_s = xty_s / lam_max      # stacked (S, p_shard) X^T theta_bar
        c_prev = xty_np / lam_max       # host view for the margin ranking
    else:
        c_prev = xty / lam_max          # X^T theta_bar
    lam_bar = lam_max
    beta_dev = jnp.zeros(p, X.dtype)
    beta_full = np.zeros(p)
    seen_keys = compile_keys if compile_keys is not None else set()
    spec_m = max(int(chunk_init), 1)

    j = 0
    while j < J and lambdas[j] >= lam_max * (1.0 - 1e-12):
        j += 1                          # beta* = 0 at/above lam_max

    while j < J:
        rem, L_rem = _pad_grid(lambdas[j:], X.dtype)
        # ---- screen the whole remaining grid in one shot ----------------
        ts = time.perf_counter()
        if screen == "none":
            fk_np = np.ones((J - j, p), dtype=bool)
        elif fshard is not None:
            # host-side Theorem-15 branch (lam_bar/lam_max are host floats):
            # the boundary normal was precomputed sharded in setup
            at_max = lam_bar >= lam_max * (1.0 - 1e-12)
            n_vec = n_boundary if at_max else (y / lam_bar - theta_bar)
            _, fk_s, _ = _tlfre_feat_jit(
                fops, Xs, specs_s, y, alpha, rem, theta_bar, n_vec,
                col_n_s, gspec_s, safety=safety)
            if screen == "gapsafe":
                beta_s = jnp.asarray(fshard.shard_features(
                    beta_full.astype(X_np.dtype)))
                resid = y - _fs.sharded_fit(fops, Xs, beta_s)
                pen = (alpha * jnp.sum(spec.weights *
                                       group_norms(spec, beta_dev))
                       + jnp.sum(jnp.abs(beta_dev)))
                radii = _gap_safe_radii_jit(y, rem, theta_bar, resid,
                                            pen) * (1.0 + safety)
                _, fk_dyn_s = _gap_safe_feat_jit(fops, specs_s, alpha,
                                                 c_prev_s, radii, col_n_s,
                                                 gspec_s)
                fk_s = fk_s & fk_dyn_s
            fk_np = fshard.unshard_features(
                np.asarray(fk_s))[:L_rem]       # one host sync
            stats.n_screens += 1
        elif not squared:
            # non-squared losses have no Theorem-12 ball; the Gap-Safe
            # ball around the latest certified dual is the only safe rule
            fit = mm(X, beta_dev)
            resid = loss.residual(y, fit)
            pen = (alpha * jnp.sum(spec.weights *
                                   group_norms(spec, beta_dev))
                   + weighted_l1(spec, beta_dev))
            radii = _gap_safe_radii_loss_jit(
                loss, y, rem, theta_bar, fit, resid, pen) * (1.0 + safety)
            _, fk = _gap_safe_grid_jit(spec, alpha, c_prev, radii,
                                       col_n, gspec, use_pallas=False)
            fk_np = np.asarray(fk)[:L_rem]      # one host sync
            stats.n_screens += 1
        else:
            n_vec = normal_vector_sgl(X, y, spec, lam_bar, lam_max,
                                      theta_bar, g_star)
            _, fk, _ = _tlfre_grid_jit(
                X, y, spec, alpha, rem, lam_bar, theta_bar, n_vec,
                col_n, gspec, safety=safety, use_pallas=pallas)
            if screen == "gapsafe":
                # both balls certify the dual optimum, so their
                # intersection screens strictly harder than either alone
                resid = y - mm(X, beta_dev)
                pen = (alpha * jnp.sum(spec.weights *
                                       group_norms(spec, beta_dev))
                       + weighted_l1(spec, beta_dev))
                radii = _gap_safe_radii_jit(y, rem, theta_bar, resid,
                                            pen) * (1.0 + safety)
                _, fk_dyn = _gap_safe_grid_jit(spec, alpha, c_prev, radii,
                                               col_n, gspec,
                                               use_pallas=pallas)
                fk = fk & fk_dyn
            fk_np = np.asarray(fk)[:L_rem]      # one host sync
            stats.n_screens += 1
            stats.n_pallas_screens += int(pallas)
        screen_time += time.perf_counter() - ts

        row_counts = fk_np.sum(axis=1)
        if row_counts[0] == 0:
            # fully-screened prefix: beta* = 0 and the dual optimum is y/lam
            k = (int(np.argmax(row_counts > 0)) if row_counts.any()
                 else len(row_counts))
            lam_bar = float(lambdas[j + k - 1])
            theta_bar = r0 / lam_bar
            if fshard is not None:
                c_prev_s = xty_s / lam_bar
                c_prev = xty_np / lam_bar
            else:
                c_prev = xty / lam_bar
            beta_dev = jnp.zeros(p, X.dtype)
            beta_full = np.zeros(p)
            j += k
            continue

        # ---- feature set: safe base + nearby-row union + ranked margin --
        base = fk_np[0]
        n_base = int(base.sum())
        p_b = _feature_bucket(n_base, p, min_bucket, margin)
        S = _expand_set(base, fk_np, p_b)
        g_S = np.unique(gid[S])
        g_b = min(_bucket(len(g_S) + 2, min_group_bucket), G + 1)
        margin_fill_sgl(S, np.asarray(c_prev), gid, sizes_np, weights_np,
                        p_b, g_b, fw_np)

        m = min(J - j, spec_m)

        # ---- bucketed reduced problem + one jitted sweep over the chunk --
        ts = time.perf_counter()
        if S.all():
            sub_spec, col_idx = spec, np.arange(p)
            if L_full is None:
                L_full = spectral_norm(X) ** 2
            X_sub, L_sub = X, L_full
            p_b, g_b = p, G
        else:
            sub_spec, col_idx = spec.bucketed_subset(S, p_b, g_b)
            X_s = np.zeros((N, p_b), dtype=X_np.dtype)
            X_s[:, :len(col_idx)] = X_np[:, col_idx]
            X_sub = jnp.asarray(X_s)
            L_sub = spectral_norm(X_sub, iters=25) ** 2
        beta0 = np.zeros(p_b, dtype=X_np.dtype)
        beta0[:len(col_idx)] = beta_full[col_idx]

        lam_chunk = lambdas[j:j + m]
        len2 = _pow2_len(m)
        # pad to a power of two so compile keys are reused; padded steps
        # are masked out via lax.cond inside the sweep
        lam_pad = np.concatenate(
            [lam_chunk, np.full(len2 - m, lam_chunk[-1])])
        valid = np.arange(len2) < m
        # the key must cover every dim jax's jit cache discriminates on —
        # a persistent compile_keys set spans problems (serving), so shape
        # and static args belong in it, not just the bucket dims; the loss
        # name rides at the END so positional readers stay valid
        if fshard is not None:
            key = ("sgl-feat", fshard.n_shards, N, p, G, str(X.dtype),
                   max_iter, check_every, fmesh is not None, p_b,
                   sub_spec.num_groups, sub_spec.max_size, len2, loss.name)
        else:
            key = ("sgl", N, p, G, str(X.dtype), max_iter, check_every,
                   pallas, p_b, sub_spec.num_groups, sub_spec.max_size, len2,
                   loss.name)
        if key not in seen_keys:
            seen_keys.add(key)
            stats.n_compilations += 1
        if fshard is not None:
            betas_b, thetas_b, cthetas_b, good_b, iters_b = _feat_sweep(
                "sgl", fops, max_iter, check_every)(
                    Xs, X_sub, y, specs_s, sub_spec, alpha, L_sub,
                    jnp.asarray(lam_pad, X.dtype), jnp.asarray(valid),
                    jnp.asarray(beta0), tol, gap_scale)
        else:
            betas_b, thetas_b, cthetas_b, good_b, iters_b = _sweep_sgl(
                X, X_sub, y, spec, sub_spec, alpha, L_sub,
                jnp.asarray(lam_pad, X.dtype), jnp.asarray(valid),
                jnp.asarray(beta0), tol, gap_scale, max_iter=max_iter,
                check_every=check_every, use_pallas=pallas, loss=loss)
        good_np = np.asarray(good_b[:m])     # one host sync
        k = int(np.argmin(good_np)) if not good_np.all() else m
        if k == 0:
            # row 0 (solved on a provably safe set) stopped at max_iter:
            # keep its best iterate so the path progresses, flagged
            k = 1
            stats.n_uncertified += 1
        stats.n_rejected += int(m - k)
        theta_bar = thetas_b[k - 1]
        if fshard is not None:
            c_prev_s = cthetas_b[k - 1]
            c_prev = fshard.unshard_features(np.asarray(c_prev_s))
        else:
            c_prev = cthetas_b[k - 1]
        betas_np = np.asarray(betas_b[:k])
        iters_np = np.asarray(iters_b[:k])
        jax.block_until_ready(theta_bar)
        solve_time += time.perf_counter() - ts

        chunk_rows = np.zeros((k, p))
        chunk_rows[:, col_idx] = betas_np[:, :len(col_idx)]
        betas[j:j + k] = chunk_rows
        iters[j:j + k] = iters_np
        kept_feat[j:j + k] = len(col_idx)       # columns entering the solver
        kept_grp[j:j + k] = len(np.unique(gid[S]))
        beta_full = chunk_rows[-1]
        beta_dev = jnp.asarray(beta_full, X.dtype)
        lam_bar = float(lam_chunk[k - 1])
        stats.n_segments += 1
        stats.buckets.append((p_b, g_b, m, k))
        spec_m = min(2 * spec_m, 64) if k == m else max(2, k)
        j += k

    return PathResult(lambdas=lambdas, betas=betas, lam_max=lam_max,
                      screen_time=screen_time, solve_time=solve_time,
                      setup_time=setup_time, iters=iters,
                      kept_features=kept_feat, kept_groups=kept_grp,
                      stats=stats)


# ---------------------------------------------------------------------------
# Nonnegative Lasso
# ---------------------------------------------------------------------------

def nn_lasso_path_batched(X, y, *, lambdas=None, n_lambdas: int = 100,
                          min_ratio: float = 0.01, screen: str = "dpc",
                          tol=1e-9, max_iter: int = 20000,
                          safety: float = 0.0, check_every: int = 10,
                          use_pallas: Optional[bool] = None,
                          min_bucket: int = 64, margin: float = 0.125,
                          chunk_init: int = 8, feature_shards: int = 0,
                          compile_keys: Optional[set] = None) -> PathResult:
    """Batched nonnegative-Lasso path: whole-grid DPC / Gap-Safe rules,
    speculative bucketed sweeps with in-scan certification.
    ``feature_shards`` / ``compile_keys`` as in ``sgl_path_batched`` (the
    nn partition is singleton-column: equal blocks when the shard count
    divides p, degraded otherwise)."""
    if screen not in ("dpc", "gapsafe", "none"):
        raise ValueError(f"unknown screen mode {screen!r}")
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    N, p = X.shape

    fshard = None
    if feature_shards and int(feature_shards) > 1:
        from ..distributed import feature_shard as _fs
        plan_fs = _fs.plan_feature_shards(int(feature_shards), p, None)
        if plan_fs.n_shards > 1:
            fshard = plan_fs
    pallas = _pallas_active(use_pallas, X.dtype) and fshard is None

    t0 = time.perf_counter()
    if fshard is not None:
        fmesh = _fs.resolve_feature_mesh(fshard.n_shards)
        fops = _fs.feature_ops(fshard.n_shards, fmesh)
        Xs = fops.place(fshard.stack_columns(np.asarray(X)))
        xty_s = _fs.sharded_xtv(fops, Xs, y)
        xty_np = fshard.unshard_features(np.asarray(xty_s))
        xty = jnp.asarray(xty_np)
        lam_max, i_star = lambda_max_nn(xty)
        lam_max = float(lam_max)
        col_n_s = _fs.sharded_column_norms(fops, Xs)
        # Theorem-21 boundary normal is the argmax COLUMN — host gather
        x_star = jnp.asarray(np.asarray(X)[:, int(i_star)])
        L_full = None
        jax.block_until_ready((col_n_s, x_star))
    else:
        xty = mm(X.T, y)
        lam_max, i_star = lambda_max_nn(xty)
        lam_max = float(lam_max)
        col_n = column_norms(X)
        L_full = spectral_norm(X) ** 2
        jax.block_until_ready((col_n, L_full))
    if lam_max <= 0:
        raise ValueError("max_i <x_i, y> <= 0: nonnegative Lasso solution is "
                         "identically zero for every lambda > 0")
    setup_time = time.perf_counter() - t0

    if lambdas is None:
        lambdas = default_lambda_grid(lam_max, n_lambdas, min_ratio)
    lambdas = np.asarray(lambdas, dtype=float)
    J = len(lambdas)

    betas = np.zeros((J, p))
    iters = np.zeros(J, dtype=np.int64)
    kept_feat = np.zeros(J, dtype=np.int64)
    stats = EngineStats()
    screen_time = 0.0
    solve_time = 0.0
    X_np = np.asarray(X)
    gap_scale = max(float(0.5 * jnp.vdot(y, y)), 1e-30)

    theta_bar = y / lam_max
    if fshard is not None:
        c_prev_s = xty_s / lam_max
        c_prev = xty_np / lam_max
    else:
        c_prev = xty / lam_max
    lam_bar = lam_max
    beta_dev = jnp.zeros(p, X.dtype)
    beta_full = np.zeros(p)
    seen_keys = compile_keys if compile_keys is not None else set()
    spec_m = max(int(chunk_init), 1)

    j = 0
    while j < J and lambdas[j] >= lam_max * (1.0 - 1e-12):
        j += 1

    while j < J:
        rem, L_rem = _pad_grid(lambdas[j:], X.dtype)
        ts = time.perf_counter()
        if screen == "none":
            fk_np = np.ones((J - j, p), dtype=bool)
        elif fshard is not None:
            at_max = lam_bar >= lam_max * (1.0 - 1e-12)
            n_vec = x_star if at_max else (y / lam_bar - theta_bar)
            fk_s, _ = _dpc_feat_jit(fops, Xs, y, rem, theta_bar, n_vec,
                                    col_n_s, safety=safety)
            if screen == "gapsafe":
                beta_s = jnp.asarray(fshard.shard_features(
                    beta_full.astype(X_np.dtype)))
                resid = y - _fs.sharded_fit(fops, Xs, beta_s)
                pen = jnp.sum(beta_dev)          # beta >= 0 => l1 = sum
                radii = _gap_safe_radii_jit(y, rem, theta_bar, resid,
                                            pen) * (1.0 + safety)
                fk_s = fk_s & _gap_safe_nn_feat_jit(fops, c_prev_s, radii,
                                                    col_n_s)
            fk_np = fshard.unshard_features(np.asarray(fk_s))[:L_rem]
            stats.n_screens += 1
        else:
            n_vec = normal_vector_nn(X, y, lam_bar, lam_max, theta_bar,
                                     i_star)
            fk, _ = _dpc_grid_jit(X, y, rem, theta_bar, n_vec, col_n,
                                  safety=safety, use_pallas=pallas)
            if screen == "gapsafe":
                resid = y - mm(X, beta_dev)
                pen = jnp.sum(beta_dev)          # beta >= 0 => l1 = sum
                radii = _gap_safe_radii_jit(y, rem, theta_bar, resid,
                                            pen) * (1.0 + safety)
                fk = fk & _gap_safe_nn_jit(c_prev, radii, col_n)
            fk_np = np.asarray(fk)[:L_rem]
            stats.n_screens += 1
            stats.n_pallas_screens += int(pallas)
        screen_time += time.perf_counter() - ts

        row_counts = fk_np.sum(axis=1)
        if row_counts[0] == 0:
            k = (int(np.argmax(row_counts > 0)) if row_counts.any()
                 else len(row_counts))
            lam_bar = float(lambdas[j + k - 1])
            theta_bar = y / lam_bar
            if fshard is not None:
                c_prev_s = xty_s / lam_bar
                c_prev = xty_np / lam_bar
            else:
                c_prev = xty / lam_bar
            beta_dev = jnp.zeros(p, X.dtype)
            beta_full = np.zeros(p)
            j += k
            continue

        base = fk_np[0]
        n_base = int(base.sum())
        p_b = _feature_bucket(n_base, p, min_bucket, margin)
        S = _expand_set(base, fk_np, p_b)
        margin_fill_nn(S, np.asarray(c_prev), p_b)

        m = min(J - j, spec_m)

        ts = time.perf_counter()
        if S.all():
            col_idx = np.arange(p)
            if L_full is None:
                L_full = spectral_norm(X) ** 2
            X_sub, L_sub = X, L_full
            p_b = p
        else:
            col_idx = np.nonzero(S)[0]
            X_s = np.zeros((N, p_b), dtype=X_np.dtype)
            X_s[:, :len(col_idx)] = X_np[:, col_idx]
            X_sub = jnp.asarray(X_s)
            L_sub = spectral_norm(X_sub, iters=25) ** 2
        beta0 = np.zeros(p_b, dtype=X_np.dtype)
        beta0[:len(col_idx)] = beta_full[col_idx]

        lam_chunk = lambdas[j:j + m]
        len2 = _pow2_len(m)
        lam_pad = np.concatenate(
            [lam_chunk, np.full(len2 - m, lam_chunk[-1])])
        valid = np.arange(len2) < m
        if fshard is not None:
            key = ("nn-feat", fshard.n_shards, N, p, str(X.dtype),
                   max_iter, check_every, fmesh is not None, p_b, len2,
                   "squared")
        else:
            key = ("nn", N, p, str(X.dtype), max_iter, check_every, pallas,
                   p_b, len2, "squared")
        if key not in seen_keys:
            seen_keys.add(key)
            stats.n_compilations += 1
        if fshard is not None:
            betas_b, thetas_b, cthetas_b, good_b, iters_b = _feat_sweep(
                "nn", fops, max_iter, check_every)(
                    Xs, X_sub, y, L_sub, jnp.asarray(lam_pad, X.dtype),
                    jnp.asarray(valid), jnp.asarray(beta0), tol, gap_scale)
        else:
            betas_b, thetas_b, cthetas_b, good_b, iters_b = _sweep_nn(
                X, X_sub, y, L_sub, jnp.asarray(lam_pad, X.dtype),
                jnp.asarray(valid), jnp.asarray(beta0), tol, gap_scale,
                max_iter=max_iter, check_every=check_every,
                use_pallas=pallas)
        good_np = np.asarray(good_b[:m])
        k = int(np.argmin(good_np)) if not good_np.all() else m
        if k == 0:
            k = 1
            stats.n_uncertified += 1
        stats.n_rejected += int(m - k)
        theta_bar = thetas_b[k - 1]
        if fshard is not None:
            c_prev_s = cthetas_b[k - 1]
            c_prev = fshard.unshard_features(np.asarray(c_prev_s))
        else:
            c_prev = cthetas_b[k - 1]
        betas_np = np.asarray(betas_b[:k])
        iters_np = np.asarray(iters_b[:k])
        jax.block_until_ready(theta_bar)
        solve_time += time.perf_counter() - ts

        chunk_rows = np.zeros((k, p))
        chunk_rows[:, col_idx] = betas_np[:, :len(col_idx)]
        betas[j:j + k] = chunk_rows
        iters[j:j + k] = iters_np
        kept_feat[j:j + k] = len(col_idx)       # columns entering the solver
        beta_full = chunk_rows[-1]
        beta_dev = jnp.asarray(beta_full, X.dtype)
        lam_bar = float(lam_chunk[k - 1])
        stats.n_segments += 1
        stats.buckets.append((p_b, 0, m, k))
        spec_m = min(2 * spec_m, 64) if k == m else max(2, k)
        j += k

    return PathResult(lambdas=lambdas, betas=betas, lam_max=lam_max,
                      screen_time=screen_time, solve_time=solve_time,
                      setup_time=setup_time, iters=iters,
                      kept_features=kept_feat, stats=stats)
