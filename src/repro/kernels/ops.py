"""Public jit'd wrappers around the Pallas kernels.

On TPU the kernels run compiled; everywhere else (a CPU host) they run in
``interpret=True`` mode, which executes the kernel body in Python/XLA for
correctness validation.  ``interpret`` lets tests pin the mode.  A shape a
kernel cannot take raises ``ValueError``; no wrapper falls back to jnp.

Dtype-purity contract (statically enforced by ``repro.analysis``):

* Every kernel is **float32-only**.  Callers gate on
  ``path_engine._pallas_active`` and the screening entry points raise
  ``TypeError`` on float64 + ``use_pallas`` (``pallas/f64-gate``); no f64
  aval may reach a ``pallas_call`` (``pallas/f64-aval``), so f64 exactness
  runs are provably kernel-free.
* Kernels never change dtype internally: f32 in, f32 out, f32 accumulate.
  Widening/narrowing happens (if ever) at the caller's boundary, never
  inside a traced body (``jaxpr/upcast-in-loop`` / ``jaxpr/f64-downcast``).
* Every block's minor two dims are a multiple of (8, 128) or the whole
  array dim (``pallas/tpu-tiling``).  Edge tiles may overhang an operand;
  kernels are written so overhanging elements only reach dropped outputs
  or are masked in-kernel, and interpret mode fills them with NaN, so the
  poisoned-padding comparisons against the ``ref.py`` oracles
  (``pallas/mask-coverage``) prove it.

Group statistics use the slot-major padded layout: ``(n_max, ..., G)``
with slot s of group g at ``[s, ..., g]`` (``core.groups.pad_slots``).
"""
from __future__ import annotations

import functools

import jax

from .xtv import xtv_pallas
from .screen_norms import dpc_screen_folds_pallas, screen_norms_pallas
from .sgl_prox import sgl_prox_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def xtv(X, v, interpret: bool | None = None):
    """out = X^T v, float32.  The certification GEMV."""
    if interpret is None:
        interpret = _interpret_default()
    return xtv_pallas(X, v, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def screen_norms_batched(c_slots, mask, interpret: bool | None = None):
    """(||S_1(c_g)||^2, ||c_g||_inf) for every grid row, fused, float32:
    c_slots (n_max, L, G) with a shared (n_max, G) mask -> ((L, G), (L, G)).

    The whole remaining lambda grid is one streaming pass (the screening
    half of the batched path engine)."""
    if interpret is None:
        interpret = _interpret_default()
    return screen_norms_pallas(c_slots, mask, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def screen_norms_folds(c_slots, mask, interpret: bool | None = None):
    """Fold-stack variant: c_slots (n_max, K, L, G) with a shared
    (n_max, G) mask -> ((K, L, G), (K, L, G)) float32.

    The (K*L, p) CV layout of the fold-batched engine: all K folds x L
    remaining lambdas are reduced in ONE kernel launch (the fold and lambda
    axes merge into the kernel's row axis), so the stacked screening GEMM's
    reduction half stays fused."""
    if interpret is None:
        interpret = _interpret_default()
    n_max, K, L, G = c_slots.shape
    snorm2, cinf = screen_norms_pallas(c_slots.reshape(n_max, K * L, G),
                                       mask, interpret=interpret)
    return snorm2.reshape(K, L, G), cinf.reshape(K, L, G)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dpc_screen_folds(C, radii, col_norms_f, interpret: bool | None = None):
    """Fused fold-stacked DPC rule: C (K, L, p), radii (K, L), col_norms_f
    (K, p) -> feat_keep (K, L, p) bool, float32 compute."""
    if interpret is None:
        interpret = _interpret_default()
    return dpc_screen_folds_pallas(C, radii, col_norms_f, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sgl_prox_padded(v_slots, mask, t_l1, t_group,
                    interpret: bool | None = None):
    """Fused SGL prox on the slot-major (n_max, G) layout, float32."""
    if interpret is None:
        interpret = _interpret_default()
    return sgl_prox_pallas(v_slots, mask, t_l1, t_group, interpret=interpret)
