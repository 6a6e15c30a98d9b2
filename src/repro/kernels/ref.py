"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics the kernels must reproduce; tests assert_allclose
kernels (interpret=True on CPU) against these for swept shapes/dtypes.
Group statistics use the slot-major ``(n_max, ..., G)`` layout of
``core.groups.pad_slots``.
"""
from __future__ import annotations

import jax.numpy as jnp


def xtv_ref(X: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """X^T v with float32 accumulation.  X: (N, p), v: (N,) -> (p,)."""
    return jnp.einsum("np,n->p", X.astype(jnp.float32), v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _slot_mask(mask, ndim):
    """(n_max, G) mask broadcast against an (n_max, ..., G) operand."""
    return mask.reshape(mask.shape[:1] + (1,) * (ndim - 2) + mask.shape[1:])


def screen_norms_ref(c_slots: jnp.ndarray, mask: jnp.ndarray):
    """Fused screening statistics over the slot-major padded layout.

    c_slots: (n_max, ..., G), mask: (n_max, G) bool.
    Returns (||S_1(c_g)||^2, ||c_g||_inf), each of shape (..., G), float32.
    """
    c = jnp.where(_slot_mask(mask, c_slots.ndim), c_slots.astype(jnp.float32),
                  0.0)
    sh = jnp.sign(c) * jnp.maximum(jnp.abs(c) - 1.0, 0.0)
    snorm2 = jnp.sum(sh * sh, axis=0)
    cinf = jnp.max(jnp.abs(c), axis=0)
    return snorm2, cinf


def sgl_prox_ref(v_slots: jnp.ndarray, mask: jnp.ndarray, t_l1: jnp.ndarray,
                 t_group: jnp.ndarray) -> jnp.ndarray:
    """Fused SGL prox on the slot-major layout.

    v_slots: (n_max, G), mask: (n_max, G), t_l1 scalar, t_group: (G,).
    Returns the padded prox output (invalid slots zero), float32.
    """
    v = jnp.where(mask, v_slots.astype(jnp.float32), 0.0)
    u = jnp.sign(v) * jnp.maximum(jnp.abs(v) - t_l1, 0.0)
    norms = jnp.sqrt(jnp.sum(u * u, axis=0))
    tg = t_group.astype(jnp.float32)
    scale = jnp.where(norms > tg, 1.0 - tg / jnp.where(norms > 0, norms, 1.0),
                      0.0)
    return u * scale[None, :]
