"""Spectral-norm utilities (power method, per paper Section 6.1.1 note).

``||X_g||_2`` per group and ``||X||_2`` for the FISTA step size.  Groups are
contiguous.  Each group of at most ``n`` columns (``GRAM_MAX_SIZE`` or
less, so that the blocks stay a small share of X) gets its norm from its
banded Gram block: ``n`` shifted column products, each one reduction over
``X``, give every ``X_g^T X_g``, and one power iteration runs over all
groups at once with groups on the lane axis.  A wider group keeps the
per-group power iteration on ``X``'s columns: one at a time in a ragged
spec, or all by a reshape and vmap where every group is wider.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .groups import GroupSpec

# Widest group whose norm comes from its banded Gram block.  The route
# holds n floats a column (the shifted products) and n^2 a group (the
# blocks) for n = min(max_size, GRAM_MAX_SIZE), and n is lowered further
# where they would pass GRAM_MAX_SHARE of X's floats (``_gram_slots``): at
# ADNI's N=747 and n_max=8 they hold 3.0% of X.
GRAM_MAX_SIZE = 32
GRAM_MAX_SHARE = 0.125
_ROW_BLOCK = 32      # rows of X per step of the shifted products


def mm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` at ``Precision.HIGHEST``: full float32 accuracy on a TPU.

    A TPU's default float32 matmul rounds the operands of a matrix-matrix
    product to bfloat16 in one pass: measured on one v5e at N=747,
    p=426,040, the (32, N) x (N, p) screening GEMM then errs by 7.2e-4 of
    ``|C| |X|`` (HIGHEST: 4.3e-8), far above the 64*eps certificate floor,
    so a rule could discard a feature that must stay.  These products are
    HBM-bound, and HIGHEST took the same time there.  Matrix-vector
    products ran in f32 either way; they go through here too, so a vmap
    that batches them into a GEMM keeps the precision.  Float64 (CPU)
    products are unchanged."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("iters", "seed"))
def spectral_norm(X: jnp.ndarray, iters: int = 50, seed: int = 0) -> jnp.ndarray:
    """||X||_2 via power iteration on X^T X."""
    p = X.shape[1]
    v = jax.random.normal(jax.random.PRNGKey(seed), (p,), dtype=X.dtype)

    def body(_, v):
        w = mm(X.T, mm(X, v))
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v / jnp.linalg.norm(v))
    return jnp.linalg.norm(mm(X, v))


def _masked_power(Xg: jnp.ndarray, mask: jnp.ndarray, iters: int) -> jnp.ndarray:
    """||Xg * mask||_2 where mask zeroes padded columns.  Xg: (N, n_max)."""
    n = Xg.shape[1]
    v0 = jnp.where(mask, 1.0, 0.0) / jnp.sqrt(jnp.maximum(jnp.sum(mask), 1))
    Xm = Xg * mask[None, :]

    def body(_, v):
        w = mm(Xm.T, mm(Xm, v))
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v0.astype(Xg.dtype))
    return jnp.linalg.norm(mm(Xm, v))


def _gram_slots(N: int, spec: GroupSpec) -> int:
    """Slots ``n`` of the Gram blocks: groups of at most ``n`` columns take
    them.  ``min(max_size, GRAM_MAX_SIZE)``, less while the (n, p) shifted
    products and (n, n, G) blocks would pass ``GRAM_MAX_SHARE`` of the
    (N, p) design's floats."""
    G, p = spec.num_groups, spec.num_features
    n = min(spec.max_size, GRAM_MAX_SIZE)
    while n > 1 and n * p + n * n * G > GRAM_MAX_SHARE * N * p:
        n -= 1
    return n


def gram_groups(X, spec: GroupSpec) -> int:
    """How many groups ``group_spectral_norms(X, spec)`` takes off Gram
    blocks; the rest run the power iteration on ``X``'s columns."""
    n = _gram_slots(X.shape[0], spec)
    if n == spec.max_size:
        return spec.num_groups
    if spec.uniform:
        return 0
    return int(np.count_nonzero(np.asarray(spec.sizes) <= n))


@functools.partial(jax.jit, static_argnames=("iters",))
def group_spectral_norms(X: jnp.ndarray, spec: GroupSpec,
                         iters: int = 30) -> jnp.ndarray:
    """(G,) spectral norms ``||X_g||_2``, by ``iters`` power iterations on
    ``X_g^T X_g`` from the uniform vector over the group's columns.

    The route is chosen per group, from static shapes and each group's
    size (``gram_groups`` counts them): a group of at most ``n`` columns
    (``_gram_slots``, at most ``GRAM_MAX_SIZE``) iterates on its Gram block
    (``_gram_group_norms``), the same iterates as on its columns to
    rounding, at a few passes over ``X`` for all groups together instead
    of one serial step per group.  A wider group keeps the iteration on
    its columns (``_block_power``), whose memory does not grow with the
    number of groups: in a ragged spec the wide groups loop, one at a
    time; where every group is wider (uniform), they all take a reshape
    and vmap."""
    N = X.shape[0]
    n = _gram_slots(N, spec)
    if n == spec.max_size:
        return _gram_group_norms(X, spec, n, iters)
    if spec.uniform:
        n = spec.max_size
        Xg = X.reshape(N, spec.num_groups, n).transpose(1, 0, 2)  # (G, N, n)
        mask = jnp.ones((spec.num_groups, n), dtype=bool)
        return jax.vmap(lambda A, m: _masked_power(A, m, iters))(Xg, mask)
    norms = _gram_group_norms(X, spec, n, iters)
    wide = spec.sizes > n
    (index,) = jnp.nonzero(wide, size=spec.num_groups, fill_value=0)

    def one_wide_group(i, norms):
        g = index[i]
        return norms.at[g].set(_block_power(
            X, spec.starts[g], spec.sizes[g], spec.max_size, iters))

    return jax.lax.fori_loop(0, jnp.sum(wide), one_wide_group, norms)


def _block_power(X: jnp.ndarray, start, size, n_max: int,
                 iters: int) -> jnp.ndarray:
    """``||X[:, start:start+size]||_2`` by power iteration on the sliced
    (N, n_max) block, ``size <= n_max``."""
    N = X.shape[0]
    # both slice indices must share the (int32) index dtype — a python
    # 0 promotes to int64 under jax_enable_x64 and dynamic_slice rejects
    # the mix
    row0 = jnp.zeros((), dtype=start.dtype)
    base = jnp.minimum(start, X.shape[1] - n_max)
    Xg = jax.lax.dynamic_slice(X, (row0, base), (N, n_max))
    # dynamic_slice clamps; rebuild the exact window mask from start/size.
    offs = jnp.arange(n_max) + base
    mask = (offs >= start) & (offs < start + size)
    Xg = jnp.where(mask[None, :], Xg, 0.0)
    return _masked_power(Xg, mask, iters)


def _shifted_products(X: jnp.ndarray, n: int) -> jnp.ndarray:
    """(n, p): row d holds ``<x_j, x_{j+d}>`` for j < p - d, zero after.

    Each lag is a reduction over shifted views of ``X``, which fuse into it
    where the compiler allows.  The rows are summed in blocks of
    ``_ROW_BLOCK``: over a taller block the CPU compiler materialises the
    product, an ``X``-sized temporary per lag.  ``tests/test_tpu_compile.py``
    holds the chip compiler's temporaries at the ADNI shape to a few
    percent of ``X``."""
    N, p = X.shape
    b = min(_ROW_BLOCK, N)

    def products(rows):
        return jnp.stack([
            jnp.pad(jnp.sum(rows[:, :p - d] * rows[:, d:], axis=0), (0, d))
            for d in range(n)])

    def body(i, acc):
        return acc + products(jax.lax.dynamic_slice_in_dim(X, i * b, b))

    S = jax.lax.fori_loop(0, N // b, body, jnp.zeros((n, p), X.dtype))
    if N % b:
        S = S + products(X[N - N % b:])
    return S


def _gram_group_norms(X: jnp.ndarray, spec: GroupSpec, n: int,
                      iters: int) -> jnp.ndarray:
    """``||X_g||_2`` from the banded Gram blocks of the first ``n`` slots,
    all groups at once (exact for groups of at most ``n`` columns).

    ``blocks[s, d, g] = <x_{s_g + s}, x_{s_g + s + d}>`` is read off the
    shifted products at lag ``d``: one 1-D gather per slot (as
    ``groups.pad_slots``, never one (G, n)-index gather).  The Gram entry
    ``(a, b)`` is ``blocks[min(a, b), |b - a|]``, so a product with it
    takes, slot by slot, row ``s`` of the block (``b >= a = s``) and its
    mirror (``a > b = s``), elementwise over the lane axis G; the blocks
    are held once, in the slot-major order the gathers write.  The
    iterate is zero on invalid slots and each product is masked to the
    valid ones, so only entries inside the group are read: the iteration
    is that of the per-group route."""
    S = _shifted_products(X, n)
    blocks = jnp.stack([S[:, spec.pad_index[:, s]]
                        for s in range(n)])             # (slot, lag, G)
    mask = spec.pad_mask[:, :n].T                       # (n, G)
    lag = jnp.arange(n)[:, None]

    def gv(v):
        def slot(s, w):
            c = jnp.where(lag + s < n, blocks[s], 0.0)  # entries (s, s + d)
            w = w.at[s].add(jnp.sum(c * jnp.roll(v, -s, axis=0), axis=0))
            mirror = jnp.where(lag > 0, c * v[s], 0.0)  # entries (s + d, s)
            return w + jnp.roll(mirror, s, axis=0)
        w = jax.lax.fori_loop(0, n, slot, jnp.zeros_like(v))
        return jnp.where(mask, w, 0.0)

    def body(_, v):
        w = gv(v)
        return w / jnp.maximum(jnp.sqrt(jnp.sum(w * w, axis=0)), 1e-30)

    v0 = jnp.where(mask, 1.0, 0.0) / jnp.sqrt(
        jnp.maximum(jnp.sum(mask, axis=0), 1))
    v = jax.lax.fori_loop(0, iters, body, v0.astype(X.dtype))
    # v^T (X_g^T X_g) v = ||X_g v||^2 for the unit iterate v
    return jnp.sqrt(jnp.maximum(jnp.sum(v * gv(v), axis=0), 0.0))


def column_norms(X: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(X * X, axis=0))


def group_frobenius_norms(X: jnp.ndarray, spec: GroupSpec) -> jnp.ndarray:
    """Cheap safe upper bound ||X_g||_2 <= ||X_g||_F (documented alternative)."""
    cn2 = jnp.sum(X * X, axis=0)
    return jnp.sqrt(jax.ops.segment_sum(cn2, spec.group_ids,
                                        num_segments=spec.num_groups))
