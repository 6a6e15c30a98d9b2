"""Spectral-norm utilities (power method, per paper Section 6.1.1 note).

``||X_g||_2`` per group and ``||X||_2`` for the FISTA step size.  Groups are
contiguous, so the ragged path slices ``X[:, start:start+n_max]`` inside a
scan; the uniform path reshapes and vmaps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import functools

from .groups import GroupSpec


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


@functools.partial(jax.jit, static_argnames=("iters",))
def group_spectral_norms(X: jnp.ndarray, spec: GroupSpec,
                         iters: int = 30) -> jnp.ndarray:
    """(G,) spectral norms ||X_g||_2."""
    N = X.shape[0]
    if spec.uniform:
        n = spec.max_size
        Xg = X.reshape(N, spec.num_groups, n).transpose(1, 0, 2)  # (G, N, n)
        mask = jnp.ones((spec.num_groups, n), dtype=bool)
        return jax.vmap(lambda A, m: _masked_power(A, m, iters))(Xg, mask)

    n_max = spec.max_size

    def body(carry, inputs):
        start, size = inputs
        # both slice indices must share the (int32) index dtype — a python
        # 0 promotes to int64 under jax_enable_x64 and dynamic_slice rejects
        # the mix
        row0 = jnp.zeros((), dtype=start.dtype)
        Xg = jax.lax.dynamic_slice(
            X, (row0, jnp.minimum(start, X.shape[1] - n_max)), (N, n_max))
        # dynamic_slice clamps; rebuild the exact window mask from start/size.
        base = jnp.minimum(start, X.shape[1] - n_max)
        offs = jnp.arange(n_max) + base
        mask = (offs >= start) & (offs < start + size)
        # roll so the group's columns sit at the front (masking handles rest)
        Xg = jnp.where(mask[None, :], Xg, 0.0)
        return carry, _masked_power(Xg, mask, iters)

    _, norms = jax.lax.scan(body, None, (spec.starts, spec.sizes))
    return norms


def column_norms(X: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(X * X, axis=0))


def group_frobenius_norms(X: jnp.ndarray, spec: GroupSpec) -> jnp.ndarray:
    """Cheap safe upper bound ||X_g||_2 <= ||X_g||_F (documented alternative)."""
    cn2 = jnp.sum(X * X, axis=0)
    return jnp.sqrt(jax.ops.segment_sum(cn2, spec.group_ids,
                                        num_segments=spec.num_groups))
