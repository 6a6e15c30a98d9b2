"""Pallas TPU kernel: tiled GEMV  out = X^T v.

This is the dominant cost of one certification (paper Theorem 17: the rule
evaluation is ``X^T o`` plus O(p) elementwise work).  The GEMV is
memory-bound (~1 FLOP per 4-byte element of X), so the kernel is a single
streaming pass over X with float32 operands and float32 accumulation at
``Precision.HIGHEST`` (no single-pass bf16 rounding of the f32 inputs):

  grid = (cdiv(p, BP), cdiv(N, BN)); the p-axis is the outer (parallel) grid
  dim, the N-axis the inner (sequential, accumulating) dim.  Each step loads
  an (BN, BP) tile of X and a (1, BN) sliver of v into VMEM and issues a
  (1, BN) @ (BN, BP) matmul into the (1, BP) f32 output tile.

X is streamed unpadded.  When N fits one tile (BN = N) nothing overhangs
the N-axis; otherwise overhanging rows of the last N tile are masked to
zero in-kernel.  Overhanging p-lanes of the last tile only feed output
lanes the partial-block write drops.  A (BN, BP) float32 tile is sized to
about 2 MiB, so double-buffered inputs stay well inside scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .screen_norms import I0, LANE, _lane_block

MAX_BLOCK_N = 1024


def _xtv_kernel(x_ref, v_ref, o_ref, *, n_rows: int, block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                 # (BN, BP)
    v = v_ref[...].astype(jnp.float32)                 # (1, BN)
    if n_rows % block_n:
        start = j * block_n
        x = jnp.where(start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
                      < n_rows, x, 0.0)
        v = jnp.where(start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
                      < n_rows, v, 0.0)
    o_ref[...] += jax.lax.dot_general(
        v, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def xtv_pallas(X: jnp.ndarray, v: jnp.ndarray, *,
               block_n: int | None = None, block_p: int | None = None,
               interpret: bool = False) -> jnp.ndarray:
    """X: (N, p), v: (N,) -> (p,) float32."""
    if X.ndim != 2 or v.shape != (X.shape[0],):
        raise ValueError(f"expected X (N, p) and v (N,); got {X.shape} / "
                         f"{v.shape}")
    N, p = X.shape
    bn = block_n if block_n is not None else (
        N if N <= MAX_BLOCK_N else MAX_BLOCK_N)
    if bn != N and bn % LANE:
        raise ValueError(f"block_n={bn} must be N or a multiple of {LANE}")
    bp = block_p if block_p is not None else _lane_block(p, bn * 4)
    out = pl.pallas_call(
        functools.partial(_xtv_kernel, n_rows=N, block_n=bn),
        grid=(pl.cdiv(p, bp), pl.cdiv(N, bn)),
        in_specs=[
            pl.BlockSpec((bn, bp), lambda i, j: (j, i)),
            pl.BlockSpec((1, bn), lambda i, j: (I0, j)),
        ],
        out_specs=pl.BlockSpec((1, bp), lambda i, j: (I0, i)),
        out_shape=jax.ShapeDtypeStruct((1, p), jnp.float32),
        interpret=interpret,
    )(X, v.astype(X.dtype)[None, :])
    return out[0]
