"""Group structure bookkeeping for Sparse-Group Lasso.

SGL groups are ragged (e.g. ADNI: 94 765 groups over 426 040 SNPs) while TPUs
want dense tiles.  ``GroupSpec`` carries both views of a contiguous group
partition of ``p`` features:

* a ragged view (``group_ids`` for segment reductions), and
* a padded dense view (``(G, n_max)`` gather indices + validity mask); the
  Pallas kernels consume it slot-major (``pad_slots``).

``weights`` generalises the paper's ``sqrt(n_g)`` group weights so that a
*reduced* problem (after feature-level screening removed some columns) keeps
the ORIGINAL group weights — required for screening to stay exact.

``feature_weights`` (optional, ``(p,)`` positive) generalises the l1 part to
the adaptive SGL penalty ``sum_f w_f |beta_f|``.  ``None`` (the default)
means the classical unweighted l1 and keeps every emitted graph identical to
the pre-adaptive engine (a ``None`` pytree child contributes no leaves).
Subset constructors carry the kept features' weights; padding columns get
weight 1.0 (they are exactly zero, so any positive weight is equivalent).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GroupSpec:
    sizes: jnp.ndarray        # (G,) int32   features per group
    starts: jnp.ndarray       # (G,) int32   offset of each (contiguous) group
    group_ids: jnp.ndarray    # (p,) int32   group index of each feature
    weights: jnp.ndarray      # (G,) float   group weights (default sqrt(n_g))
    pad_index: jnp.ndarray    # (G, n_max) int32 gather indices into [0, p)
    pad_mask: jnp.ndarray     # (G, n_max) bool  validity of padded slots
    num_groups: int           # static
    num_features: int         # static
    max_size: int             # static
    uniform: bool             # static: all groups share one size
    feature_weights: object = None   # (p,) float adaptive l1 weights, or None

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        children = (self.sizes, self.starts, self.group_ids, self.weights,
                    self.pad_index, self.pad_mask, self.feature_weights)
        aux = (self.num_groups, self.num_features, self.max_size, self.uniform)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:6], *aux, children[6])

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_sizes(cls, sizes: Sequence[int], weights=None,
                   feature_weights=None) -> "GroupSpec":
        sizes_np = np.asarray(sizes, dtype=np.int32)
        if sizes_np.ndim != 1 or (sizes_np <= 0).any():
            raise ValueError("group sizes must be a 1-D positive vector")
        G = int(sizes_np.shape[0])
        p = int(sizes_np.sum())
        starts_np = np.concatenate([[0], np.cumsum(sizes_np)[:-1]]).astype(np.int32)
        gid_np = np.repeat(np.arange(G, dtype=np.int32), sizes_np)
        n_max = int(sizes_np.max())
        pad_idx = starts_np[:, None] + np.arange(n_max, dtype=np.int32)[None, :]
        pad_mask = np.arange(n_max)[None, :] < sizes_np[:, None]
        pad_idx = np.where(pad_mask, pad_idx, 0).astype(np.int32)
        if weights is None:
            w_np = np.sqrt(sizes_np.astype(np.float64))
        else:
            w_np = np.asarray(weights, dtype=np.float64)
            if w_np.shape != (G,):
                raise ValueError("weights must have shape (G,)")
        if feature_weights is not None:
            fw_np = np.asarray(feature_weights, dtype=np.float64)
            if fw_np.shape != (p,):
                raise ValueError("feature_weights must have shape (p,)")
            if (fw_np <= 0).any():
                raise ValueError("feature_weights must be strictly positive")
            fw = jnp.asarray(fw_np)
        else:
            fw = None
        return cls(
            sizes=jnp.asarray(sizes_np),
            starts=jnp.asarray(starts_np),
            group_ids=jnp.asarray(gid_np),
            weights=jnp.asarray(w_np),
            pad_index=jnp.asarray(pad_idx),
            pad_mask=jnp.asarray(pad_mask),
            num_groups=G,
            num_features=p,
            max_size=n_max,
            uniform=bool((sizes_np == sizes_np[0]).all()),
            feature_weights=fw,
        )

    @classmethod
    def uniform_groups(cls, num_groups: int, group_size: int) -> "GroupSpec":
        return cls.from_sizes([group_size] * num_groups)

    # -- subsetting (for physically reduced problems) -------------------------
    def bucketed_subset(self, feat_keep: np.ndarray, p_bucket: int,
                        g_bucket: int) -> tuple["GroupSpec", np.ndarray]:
        """Reduced spec padded to fixed shapes (p_bucket, g_bucket) so jitted
        solvers are compiled once per bucket rather than once per lambda.

        Padding columns are zero columns of the padded design matrix; they are
        assigned to the trailing 'garbage bin' group ``g_bucket - 1``.  Zero
        columns have zero gradient and zero shrinkage, so their coefficients
        provably stay zero under the prox — the padded problem restricted to
        the real columns IS the reduced problem.
        """
        feat_keep = np.asarray(feat_keep, dtype=bool)
        col_idx = np.nonzero(feat_keep)[0]
        p_kept = len(col_idx)
        if p_kept > p_bucket:
            raise ValueError("p_bucket too small")
        gid_kept = np.asarray(self.group_ids)[col_idx]
        kept_groups, inv, counts = np.unique(gid_kept, return_inverse=True,
                                             return_counts=True)
        G_kept = len(kept_groups)
        pad = p_bucket - p_kept
        # an exact fit (every bucket slot holds a real group, no padding
        # columns) needs no garbage bin; only reject when a non-empty bin
        # would have nowhere to live
        if G_kept > g_bucket or (G_kept == g_bucket and pad > 0):
            raise ValueError("g_bucket too small")
        w_full = np.asarray(self.weights)

        # fixed padded width: bucket shape must not depend on which groups
        # survived, so reuse the parent's max_size
        n_max = self.max_size

        sizes = np.zeros(g_bucket, dtype=np.int32)
        sizes[:G_kept] = counts
        weights = np.ones(g_bucket, dtype=np.float64)
        weights[:G_kept] = w_full[kept_groups]

        group_ids = np.full(p_bucket, g_bucket - 1, dtype=np.int32)
        # kept columns are laid out group-contiguously
        order = np.argsort(inv, kind="stable")
        group_ids[:p_kept] = inv[order]
        col_idx = col_idx[order]
        starts = np.zeros(g_bucket, dtype=np.int32)
        starts[:G_kept] = np.concatenate([[0], np.cumsum(counts)[:-1]])
        if G_kept < g_bucket:
            sizes[g_bucket - 1] = pad        # garbage bin (may exceed n_max;
            #                                 its columns are all-zero so the
            #                                 truncated padded view is exact)
            starts[g_bucket - 1] = p_kept

        pad_idx = starts[:, None] + np.arange(n_max, dtype=np.int32)[None, :]
        pad_mask = np.arange(n_max)[None, :] < np.minimum(sizes, n_max)[:, None]
        pad_idx = np.where(pad_mask, np.minimum(pad_idx, p_bucket - 1), 0)

        if self.feature_weights is not None:
            # padding columns are exactly zero, so their l1 weight (1.0) is
            # inert; kept columns carry their original adaptive weight
            fw_full = np.asarray(self.feature_weights)
            fw = np.ones(p_bucket, dtype=np.float64)
            fw[:p_kept] = fw_full[col_idx]
            fw = jnp.asarray(fw)
        else:
            fw = None

        spec = GroupSpec(
            sizes=jnp.asarray(sizes), starts=jnp.asarray(starts),
            group_ids=jnp.asarray(group_ids), weights=jnp.asarray(weights),
            pad_index=jnp.asarray(pad_idx.astype(np.int32)),
            pad_mask=jnp.asarray(pad_mask),
            num_groups=g_bucket, num_features=p_bucket, max_size=n_max,
            uniform=False, feature_weights=fw)
        return spec, col_idx

    def subset(self, feat_keep: np.ndarray) -> tuple["GroupSpec", np.ndarray]:
        """Reduced spec over kept features.

        Keeps the ORIGINAL group weight for every surviving group (screened
        features are provably zero, so the group norm over the survivors
        equals the group norm over the full group).  Returns (spec, col_idx)
        where ``col_idx`` maps reduced columns back to original columns.
        """
        feat_keep = np.asarray(feat_keep, dtype=bool)
        col_idx = np.nonzero(feat_keep)[0]
        gid = np.asarray(self.group_ids)[col_idx]
        w_full = np.asarray(self.weights)
        kept_groups, counts = np.unique(gid, return_counts=True)
        fw = (None if self.feature_weights is None
              else np.asarray(self.feature_weights)[col_idx])
        spec = GroupSpec.from_sizes(counts, weights=w_full[kept_groups],
                                    feature_weights=fw)
        return spec, col_idx


# ---------------------------------------------------------------------------
# Segment reductions over the ragged view.
# ---------------------------------------------------------------------------

def group_sum(spec: GroupSpec, x: jnp.ndarray) -> jnp.ndarray:
    """Per-group sum of a (p,) vector -> (G,)."""
    return jax.ops.segment_sum(x, spec.group_ids, num_segments=spec.num_groups)


def group_norms(spec: GroupSpec, x: jnp.ndarray) -> jnp.ndarray:
    """Per-group l2 norms -> (G,)."""
    return jnp.sqrt(group_sum(spec, x * x))


def group_max_abs(spec: GroupSpec, x: jnp.ndarray) -> jnp.ndarray:
    """Per-group l_inf norms -> (G,)."""
    return jax.ops.segment_max(jnp.abs(x), spec.group_ids,
                               num_segments=spec.num_groups)


def pad_groups(spec: GroupSpec, x: jnp.ndarray) -> jnp.ndarray:
    """(p,) -> padded (G, n_max); invalid slots are zero.  Gathered one slot
    at a time (``pad_slots``): the TPU compiler took 25 s over the single
    (G, n_max)-index gather at p=426,040 and under 1 s over this."""
    return jnp.where(spec.pad_mask, pad_slots(spec, x).T, 0.0)


def pad_slots(spec: GroupSpec, x: jnp.ndarray) -> jnp.ndarray:
    """(..., p) -> slot-major padded (n_max, ..., G): slot s of group g at
    ``[s, ..., g]``, the layout of the Pallas kernels (groups on lanes, so
    only ragged groups pad).  Invalid slots hold an arbitrary column; the
    kernels mask them with ``spec.pad_mask.T``.  One column gather per slot
    writes straight into the stacked result (no transposed temporary, and
    no multi-dimensional gather, which compiles slowly on a TPU)."""
    return jnp.stack([x[..., spec.pad_index[:, s]]
                      for s in range(spec.max_size)])


def broadcast_to_features(spec: GroupSpec, g: jnp.ndarray) -> jnp.ndarray:
    """(G,) per-group values -> (p,) per-feature values."""
    return g[spec.group_ids]
