"""Group-aligned column sharding of the design matrix for feature-parallel
two-layer screening (TLFre Thms 15/16, DPC Thm 22, Gap-Safe).

Past the single-device capacity wall (``python -m repro.analysis
--capacity``: max p ~ 1.9M f32 at N=1000 / 16 GB) the only lever left is
sharding X column-wise: every screening quantity — the per-segment
``(K*L, N) x (N, p)`` grid GEMM, the group-stat reductions, the Theorem-22
threshold, and the in-scan certification GEMV ``X^T rho`` — is independent
per feature (per group), so a column partition parallelises them with NO
communication; the only collectives the sharded layer ever fires are psums
of N-vectors (``X @ v`` fits, boundary normal vectors, spectral-norm power
iterations).  The solve bucket stays single-device: surviving columns are
gathered host-side exactly as in the unsharded engine.

Partition layout
----------------
Shard ``s`` of ``S`` owns the contiguous group block
``[s*G/S, (s+1)*G/S)`` — groups are NEVER split across shards, so every
per-group quantity (shrink roots, group norms, spectral norms) is computed
entirely locally from the shard's own columns.  ``S`` degrades to the
largest count that divides the group count, via exactly the predicate
``distributed.sharding.divisible`` (the ZeRO/TP degrading rule the Layer-4
shard verifier checks).  Ragged group sizes make block widths unequal;
blocks are zero-padded to the widest (``p_shard``), and the pad columns are
arithmetically inert by construction:

* the local ``GroupSpec`` keeps the REAL sizes/starts/pad_index/pad_mask of
  its groups (so ``pad_groups`` never reads a pad column and power-iteration
  normalisation is bitwise-identical to the global computation); only
  ``group_ids`` maps pad columns — onto the last local group, where zero
  entries add exact ``0.0`` terms to segment sums (IEEE: ``x + 0.0 == x``)
  and ``0.0`` terms to segment maxima of nonnegative stats;
* pad columns of X are zero, so their screening stats (``|c| = 0``,
  ``col_norm = 0``) can never pass a keep rule.

Hence sharded group stats are bit-exact against the single-device path in
f64 and agree to rounding in f32 (same summation order per group — the only
difference is which GEMM call computes each column).

Execution
---------
``FeatureOps`` runs the per-shard programs either under ``shard_map`` on a
1-D 'feature' mesh (``launch.mesh.make_feature_mesh``) or, when the host
lacks devices, as a ``vmap`` over the stacked ``(S, ...)`` shard blocks —
identical math and layout, one device, which is also what the forced-8-
device parity suite compares against.  ``fsum`` is the single psum site.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .sharding import divisible
from ..core.groups import GroupSpec
from ..core.linalg import mm


def effective_shards(n_units: int, requested: int) -> int:
    """Largest shard count <= ``requested`` dividing ``n_units`` (group
    count for SGL, feature count for nn-lasso), degrading exactly like
    ``distributed.sharding.divisible``; 1 when nothing > 1 divides."""
    req = int(requested)
    for c in range(min(req, int(n_units)), 1, -1):
        if divisible(int(n_units), {"feature": c}, "feature"):
            return c
    return 1


def shard_width_bound(p: int, n_units: int, n_shards: int,
                      max_size: int) -> int:
    """Static upper bound on the padded block width ``p_shard`` from shape
    data alone: a block holds ``n_units // n_shards`` groups of at most
    ``max_size`` columns.  Exact for uniform groups; the resource audit
    prices the sharded keys at this envelope so per-device cost cards never
    under-estimate the real block."""
    if n_shards <= 1:
        return int(p)
    g_sh = max(int(n_units) // int(n_shards), 1)
    return min(int(p), g_sh * int(max_size))


def _local_spec(spec_np: dict, g0: int, g1: int, col0: int, p_shard: int,
                n_max: int, uniform: bool) -> GroupSpec:
    """Local GroupSpec of the block [g0, g1) re-based to column 0.

    Real sizes/starts (NOT extended over pad columns) keep every padded
    per-group computation bitwise-identical to the global one; pad columns
    get group_id G_loc-1 (inert zeros, see module docstring)."""
    G_loc = g1 - g0
    sizes = spec_np["sizes"][g0:g1]
    starts = (spec_np["starts"][g0:g1] - col0).astype(np.int32)
    width = int(sizes.sum())
    gid = np.full(p_shard, G_loc - 1, dtype=np.int32)
    gid[:width] = spec_np["group_ids"][col0:col0 + width] - g0
    pad_idx = starts[:, None] + np.arange(n_max, dtype=np.int32)[None, :]
    pad_mask = np.arange(n_max)[None, :] < sizes[:, None]
    pad_idx = np.where(pad_mask, pad_idx, 0).astype(np.int32)
    return GroupSpec(
        sizes=jnp.asarray(sizes), starts=jnp.asarray(starts),
        group_ids=jnp.asarray(gid), weights=jnp.asarray(spec_np["weights"][g0:g1]),
        pad_index=jnp.asarray(pad_idx), pad_mask=jnp.asarray(pad_mask),
        num_groups=G_loc, num_features=p_shard, max_size=n_max,
        uniform=bool(uniform))


@dataclasses.dataclass(frozen=True)
class FeatureShardPlan:
    """Static description of one group-aligned column partition."""
    requested: int
    n_shards: int
    p: int
    n_units: int              # groups (SGL) or features (nn-lasso)
    p_shard: int              # padded per-block width (max real width)
    units_per_shard: int
    col_starts: np.ndarray    # (S,) first original column of each block
    widths: np.ndarray        # (S,) real column count of each block
    specs_stacked: Optional[GroupSpec]   # leaves lead with S; None for nn

    @property
    def col_mask(self) -> np.ndarray:
        """(S, p_shard) validity of each padded block slot."""
        return (np.arange(self.p_shard)[None, :]
                < np.asarray(self.widths)[:, None])

    # -- host-side layout shuttles -----------------------------------------
    def stack_columns(self, X: np.ndarray) -> np.ndarray:
        """(N, p) -> (S, N, p_shard), blocks zero-padded on the right."""
        X = np.asarray(X)
        out = np.zeros((self.n_shards, X.shape[0], self.p_shard), X.dtype)
        for s in range(self.n_shards):
            c0, w = int(self.col_starts[s]), int(self.widths[s])
            out[s, :, :w] = X[:, c0:c0 + w]
        return out

    def shard_features(self, v: np.ndarray) -> np.ndarray:
        """(..., p) -> (S, ..., p_shard) host scatter (pads zero)."""
        v = np.asarray(v)
        out = np.zeros((self.n_shards,) + v.shape[:-1] + (self.p_shard,),
                       v.dtype)
        for s in range(self.n_shards):
            c0, w = int(self.col_starts[s]), int(self.widths[s])
            out[s, ..., :w] = v[..., c0:c0 + w]
        return out

    def unshard_features(self, a) -> np.ndarray:
        """(S, ..., p_shard) -> (..., p) host gather dropping pads."""
        a = np.asarray(a)
        out = np.zeros(a.shape[1:-1] + (self.p,), a.dtype)
        for s in range(self.n_shards):
            c0, w = int(self.col_starts[s]), int(self.widths[s])
            out[..., c0:c0 + w] = a[s, ..., :w]
        return out

    def stacked_index(self, cols) -> np.ndarray:
        """Original columns -> their positions in the flattened
        (S * p_shard) stacked layout."""
        cols = np.asarray(cols)
        s = np.searchsorted(np.asarray(self.col_starts), cols,
                            side="right") - 1
        return (s * self.p_shard + cols
                - np.asarray(self.col_starts)[s]).astype(np.int32)

    def shard_groups(self, a) -> np.ndarray:
        """(..., G) -> (S, ..., G_shard) host scatter (contiguous blocks,
        no padding — every shard owns exactly ``units_per_shard`` groups)."""
        a = np.asarray(a)
        g = self.units_per_shard
        return np.stack([a[..., s * g:(s + 1) * g]
                         for s in range(self.n_shards)])

    def unshard_groups(self, a) -> np.ndarray:
        """(S, ..., G_shard) -> (..., G): blocks are contiguous groups."""
        a = np.asarray(a)
        return np.concatenate([a[s] for s in range(self.n_shards)], axis=-1)


def plan_feature_shards(requested: int, p: int,
                        spec: Optional[GroupSpec] = None) -> FeatureShardPlan:
    """Build the group-aligned partition (or singleton-column partition for
    nn-lasso when ``spec`` is None), degrading the shard count per
    ``effective_shards``."""
    n_units = int(spec.num_groups) if spec is not None else int(p)
    S = effective_shards(n_units, requested)
    if spec is None:
        w = p // S
        widths = np.full(S, w, dtype=np.int64)
        col_starts = np.arange(S, dtype=np.int64) * w
        return FeatureShardPlan(
            requested=int(requested), n_shards=S, p=int(p), n_units=n_units,
            p_shard=w, units_per_shard=w, col_starts=col_starts,
            widths=widths, specs_stacked=None)
    G_sh = n_units // S
    spec_np = {k: np.asarray(getattr(spec, k))
               for k in ("sizes", "starts", "group_ids", "weights")}
    g_lo = np.arange(S, dtype=np.int64) * G_sh
    col_starts = spec_np["starts"][g_lo].astype(np.int64)
    ends = np.concatenate([col_starts[1:], [p]])
    widths = ends - col_starts
    p_shard = int(widths.max())
    locals_ = [
        _local_spec(spec_np, int(g_lo[s]), int(g_lo[s]) + G_sh,
                    int(col_starts[s]), p_shard, spec.max_size, spec.uniform)
        for s in range(S)
    ]
    # the sharded route is unweighted-only (guarded by the engines), so
    # the feature_weights child stays a literal None across the stack
    leaves = [jnp.stack([ls.tree_flatten()[0][i] for ls in locals_])
              for i in range(6)] + [None]
    stacked = GroupSpec.tree_unflatten(locals_[0].tree_flatten()[1],
                                       tuple(leaves))
    return FeatureShardPlan(
        requested=int(requested), n_shards=S, p=int(p), n_units=n_units,
        p_shard=p_shard, units_per_shard=G_sh, col_starts=col_starts,
        widths=widths, specs_stacked=stacked)


# ---------------------------------------------------------------------------
# Executor: shard_map over a 'feature' mesh, or vmap over stacked blocks.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FeatureOps:
    """Maps per-shard programs over stacked ``(S, ...)`` shard blocks.

    ``mesh`` is a 1-D 'feature' mesh (real or Abstract) — or ``None`` for
    the single-device vmap executor.  Hashable, so jitted callers can take
    an instance as a static argument and the fold-sweep caches can key on
    it."""
    n_shards: int
    mesh: object = None

    def _shard_map(self, wrapped, n_rep, reduce_out):
        from jax.sharding import PartitionSpec as P
        out_specs = P() if reduce_out else P("feature")
        return jax.shard_map(
            wrapped, mesh=self.mesh,
            in_specs=(P("feature"),) + (P(),) * n_rep,
            out_specs=out_specs, check_vma=False)

    def place(self, x):
        """Put a stacked ``(S, ...)`` host array where the executor reads
        it: one block per device of the 'feature' mesh, or one device for
        the vmap executor."""
        if self.mesh is None:
            return jnp.asarray(x)
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(x, NamedSharding(self.mesh, P("feature")))

    def fmap(self, body, sharded, *replicated):
        """``body(local_block, *replicated) -> local_out`` mapped over the
        leading shard axis of every leaf of ``sharded``; outputs keep the
        leading shard axis.  Feature-local: fires no collective."""
        if self.mesh is None:
            return jax.vmap(lambda sh: body(sh, *replicated))(sharded)

        def wrapped(sh, *rep):
            loc = jax.tree_util.tree_map(lambda x: x[0], sh)
            out = body(loc, *rep)
            return jax.tree_util.tree_map(lambda x: x[None], out)

        return self._shard_map(wrapped, len(replicated), False)(
            sharded, *replicated)

    def fsum(self, body, sharded, *replicated):
        """Shard-wise partial results summed across the feature axis — the
        ONE collective (psum) the sharded layer is allowed."""
        if self.mesh is None:
            parts = jax.vmap(lambda sh: body(sh, *replicated))(sharded)
            return jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0),
                                          parts)

        def wrapped(sh, *rep):
            loc = jax.tree_util.tree_map(lambda x: x[0], sh)
            out = body(loc, *rep)
            return jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, "feature"), out)

        return self._shard_map(wrapped, len(replicated), True)(
            sharded, *replicated)


_OPS_CACHE: dict = {}


def feature_ops(n_shards: int, mesh=None) -> FeatureOps:
    ops = _OPS_CACHE.get((n_shards, mesh))
    if ops is None:
        ops = _OPS_CACHE[(n_shards, mesh)] = FeatureOps(n_shards, mesh)
    return ops


def resolve_feature_mesh(n_shards: int):
    """Real 'feature' mesh when the host has the devices, else None (vmap
    executor)."""
    if n_shards <= 1:
        return None
    from ..launch.mesh import make_feature_mesh
    return make_feature_mesh(n_shards)


# ---------------------------------------------------------------------------
# Sharded numerical primitives (each a thin composition of fmap/fsum).
# ---------------------------------------------------------------------------

def sharded_xtv(ops: FeatureOps, Xs, v):
    """Stacked correlations ``(S, p_shard)``: each shard's ``X_blk^T v``."""
    return ops.fmap(lambda Xb, vv: mm(Xb.T, vv), Xs, v)


def sharded_fit(ops: FeatureOps, Xs, v_s):
    """``X @ v`` from a stacked coefficient layout ``(S, p_shard)`` (or
    ``(S, K, p_shard)`` fold-stacked, giving ``(K, N)``) — partial GEMV per
    shard + psum; pad columns multiply zero coefficients."""
    def body(loc):
        Xb, vb = loc
        return mm(vb, Xb.T) if vb.ndim > 1 else mm(Xb, vb)
    return ops.fsum(body, (Xs, v_s))


def sharded_column_norms(ops: FeatureOps, Xs):
    from ..core.linalg import column_norms
    return ops.fmap(column_norms, Xs)


def sharded_group_spectral_norms(ops: FeatureOps, Xs, specs, iters: int = 30):
    from ..core.linalg import group_spectral_norms

    def body(loc):
        Xb, spec_loc = loc
        return group_spectral_norms(Xb, spec_loc, iters=iters)
    return ops.fmap(body, (Xs, specs))


def sharded_group_frobenius_norms(ops: FeatureOps, Xs, specs):
    from ..core.linalg import group_frobenius_norms

    def body(loc):
        Xb, spec_loc = loc
        return group_frobenius_norms(Xb, spec_loc)
    return ops.fmap(body, (Xs, specs))


@functools.partial(jax.jit, static_argnames=("ops", "iters", "seed"))
def sharded_spectral_norm(ops: FeatureOps, Xs, col_mask_s, iters: int = 50,
                          seed: int = 0):
    """||X||_2 by power iteration over the sharded columns.  Per step: one
    psum of the N-vector ``u = sum_s X_blk v_blk`` and a feature-local
    back-projection; pad slots stay exactly zero (zero columns of X).
    Random start like ``linalg.spectral_norm`` (a structured start can sit
    near-orthogonal to the top eigenvector and under-estimate ||X|| — the
    unsafe direction for a FISTA step size)."""
    v = jax.random.normal(jax.random.PRNGKey(seed), Xs.shape[::2],
                          Xs.dtype)
    v = jnp.where(col_mask_s, v, 0.0)
    v = v / jnp.maximum(jnp.sqrt(jnp.sum(v * v)), 1e-30)

    def step(_, v):
        u = sharded_fit(ops, Xs, v)
        w = ops.fmap(lambda Xb, uu: mm(Xb.T, uu), Xs, u)
        return w / jnp.maximum(jnp.sqrt(jnp.sum(w * w)), 1e-30)

    v = jax.lax.fori_loop(0, iters, step, v)
    u = sharded_fit(ops, Xs, v)
    return jnp.sqrt(jnp.sum(u * u))


def cert_sgl(ops: FeatureOps, Xs, specs, rho, alpha):
    """Sharded SGL certification: stacked ``c = X^T rho`` plus the global
    dual-scaling factor.  Per-group shrink roots are feature-local; the
    global ``s = min_g`` is taken on the gathered (S, G_shard) stack, and
    ``min`` is exactly associative, so ``s`` is bitwise-equal to
    ``dual_scaling_sgl`` on one device."""
    from ..core.lambda_max import group_shrink_roots

    def body(loc, rho, alpha):
        Xb, spec_loc = loc
        c = mm(Xb.T, rho)
        return c, group_shrink_roots(spec_loc, c, alpha)

    c_s, roots = ops.fmap(body, (Xs, specs), rho, jnp.asarray(alpha))
    s = jnp.min(jnp.where(roots > 1.0, 1.0 / roots, 1.0))
    return c_s, s


def cert_nn(ops: FeatureOps, Xs, rho):
    """Sharded nn-lasso certification (``dual_scaling_nn``): pad columns
    contribute ``c = 0`` to the max, which can never push it above 1, so
    ``s`` matches the single-device value bitwise."""
    c_s = sharded_xtv(ops, Xs, rho)
    m = jnp.max(c_s)
    s = jnp.where(m > 1.0, 1.0 / m, 1.0)
    return c_s, s
