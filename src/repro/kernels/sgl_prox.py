"""Pallas TPU kernel: fused two-level SGL prox (soft-threshold -> group scale).

One FISTA iteration applies  prox_{t(lam1 Omega1 + lam2 Omega2)}  to a
p-vector.  Unfused, that is 3 HBM passes (shrink; group-norm reduce; scale).
Fused on the slot-major padded layout ``(n_max, G)`` (slot s of group g at
``[s, g]``; groups on lanes, see ``screen_norms``) it is a single
VMEM-resident pass:

    u     = S_{t_l1}(v)            elementwise
    n_g   = ||u_g||_2              reduce over the slot axis
    out_g = (1 - t_group_g/n_g)_+ u_g   broadcast over the slot axis

Grid over lane blocks of groups; each step holds an ``(n_max, BG)`` tile and
a ``(1, BG)`` row of group thresholds.  Overhanging edge lanes compute on
unspecified values and are dropped by the partial-block write.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .screen_norms import I0, _lane_block


def _sgl_prox_kernel(v_ref, m_ref, tg_ref, tl1_ref, o_ref):
    t_l1 = tl1_ref[0, 0]
    v = jnp.where(m_ref[...] != 0, v_ref[...].astype(jnp.float32), 0.0)
    u = jnp.sign(v) * jnp.maximum(jnp.abs(v) - t_l1, 0.0)
    norms = jnp.sqrt(jnp.sum(u * u, axis=0, keepdims=True))   # (1, BG)
    tg = tg_ref[...].astype(jnp.float32)
    scale = jnp.where(norms > tg,
                      1.0 - tg / jnp.where(norms > 0, norms, 1.0), 0.0)
    o_ref[...] = u * scale


def sgl_prox_pallas(v_slots: jnp.ndarray, mask: jnp.ndarray, t_l1, t_group,
                    *, block_g: int | None = None, interpret: bool = False
                    ) -> jnp.ndarray:
    """v_slots: (n_max, G), mask (n_max, G), t_l1 scalar, t_group: (G,)
    -> (n_max, G) f32, exactly zero in the invalid slots."""
    if v_slots.ndim != 2 or mask.shape != v_slots.shape:
        raise ValueError(f"expected v_slots and mask of one (n_max, G) "
                         f"shape; got {v_slots.shape} / {mask.shape}")
    n_max, G = v_slots.shape
    bg = block_g if block_g is not None else _lane_block(G, n_max * 4)
    tg = jnp.asarray(t_group, jnp.float32).reshape(1, G)
    tl1 = jnp.asarray(t_l1, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _sgl_prox_kernel,
        grid=(pl.cdiv(G, bg),),
        in_specs=[
            pl.BlockSpec((n_max, bg), lambda i: (I0, i)),
            pl.BlockSpec((n_max, bg), lambda i: (I0, i)),
            pl.BlockSpec((1, bg), lambda i: (I0, i)),
            pl.BlockSpec((1, 1), lambda i: (I0, I0)),
        ],
        out_specs=pl.BlockSpec((n_max, bg), lambda i: (I0, i)),
        out_shape=jax.ShapeDtypeStruct((n_max, G), jnp.float32),
        interpret=interpret,
    )(v_slots, mask.astype(jnp.int32), tg, tl1)
