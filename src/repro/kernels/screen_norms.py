"""Pallas TPU kernels: fused screening statistics.

After the screening GEMM C = centers @ X, TLFre needs per (row l, group g):
    ||S_1(c_lg)||^2   (Theorem 15 branch 1)
    ||c_lg||_inf      (Theorem 15 branch selection + branch 2)
and per feature |c_i| (Theorem 16 — already available as |C|).

A naive jnp implementation reads C from HBM three times (shrink,
square-reduce, max-reduce).  ``screen_norms_pallas`` fuses it into ONE
streaming pass over the **slot-major** padded layout ``(n_max, L, G)``:
slot s of group g sits at ``[s, :, g]``.  Groups lie on lanes and grid rows
on sublanes, so the only padding is ragged groups up to ``n_max`` slots (an
untiled leading axis): the operand holds ``n_max * G / p`` times the bytes
of C.  The group-major ``(L, G, n_max)`` layout this replaces put ``n_max``
on lanes, where TPU tiling pads it to 128 — about 13x the bytes of C for
gene-sized groups of ~4.5 SNPs.  Each grid step loads an
``(n_max, BL, BG)`` tile, masks invalid slots, and reduces over the leading
axis (elementwise adds across vreg tiles) into two ``(BL, BG)`` outputs.

Edge tiles may overhang L and G: every output element depends only on its
own (l, g) column of slots, so overhang rows/lanes read unspecified values
and their outputs are dropped by the partial-block write.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


SUBLANE = 8
LANE = 128
# block index 0 of an index map: a Python 0 traces as int64 under
# jax_enable_x64, which Mosaic refuses to compile
I0 = np.int32(0)
# per-operand tile budget: double-buffered inputs stay well inside v5e's
# 16 MiB scoped VMEM
TILE_BYTES = 2 * 1024 * 1024
MAX_BLOCK_G = 2048


def _row_block(n: int, want: int = SUBLANE) -> int:
    """A sublane block: the whole (short) dim, or a multiple of 8."""
    return n if n <= want else want


def _lane_block(n: int, width_bytes: int) -> int:
    """A lane block sized to ``TILE_BYTES`` for a tile whose other dims
    hold ``width_bytes`` per lane: the whole dim if it fits, else a
    multiple of 128."""
    fit = (TILE_BYTES // max(width_bytes, 1)) // LANE * LANE
    if fit < LANE:
        raise ValueError(
            f"a {width_bytes}-byte lane column does not fit a "
            f"{TILE_BYTES}-byte VMEM tile; the fused kernel cannot take "
            f"groups this large")
    want = min(fit, MAX_BLOCK_G)
    return n if n <= want else want


def _screen_norms_kernel(c_ref, m_ref, s_ref, i_ref):
    valid = m_ref[...] != 0                               # (n_max, 1, BG)
    a = jnp.where(valid, jnp.abs(c_ref[...].astype(jnp.float32)), 0.0)
    sh = jnp.maximum(a - 1.0, 0.0)
    s_ref[...] = jnp.sum(sh * sh, axis=0)
    i_ref[...] = jnp.max(a, axis=0)


def screen_norms_pallas(c_slots: jnp.ndarray, mask: jnp.ndarray, *,
                        block_l: int = SUBLANE, block_g: int | None = None,
                        interpret: bool = False):
    """Fused screening statistics on the slot-major layout.

    ``c_slots``: (n_max, L, G) — every grid row's correlations gathered by
    ``GroupSpec.pad_index.T``; ``mask``: (n_max, G) validity of the slots
    (shared by every row).  Returns ``(snorm2 (L, G), cinf (L, G))``
    float32.  Invalid slots are masked, whatever they hold."""
    if c_slots.ndim != 3 or mask.shape != (c_slots.shape[0],
                                           c_slots.shape[2]):
        raise ValueError(f"expected c_slots (n_max, L, G) and mask "
                         f"(n_max, G); got {c_slots.shape} / {mask.shape}")
    n_max, L, G = c_slots.shape
    bl = _row_block(L, block_l)
    bg = block_g if block_g is not None else _lane_block(G, n_max * bl * 4)
    snorm2, cinf = pl.pallas_call(
        _screen_norms_kernel,
        grid=(pl.cdiv(L, bl), pl.cdiv(G, bg)),
        in_specs=[
            pl.BlockSpec((n_max, bl, bg), lambda i, j: (I0, i, j)),
            pl.BlockSpec((n_max, 1, bg), lambda i, j: (I0, I0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bl, bg), lambda i, j: (i, j)),
            pl.BlockSpec((bl, bg), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, G), jnp.float32),
            jax.ShapeDtypeStruct((L, G), jnp.float32),
        ],
        interpret=interpret,
    )(c_slots, mask.astype(jnp.int32)[:, None, :])
    return snorm2, cinf


# ---------------------------------------------------------------------------
# Fold-stacked DPC rule: fused omega = C + r * ||x_i|| threshold
# ---------------------------------------------------------------------------

def _dpc_screen_folds_kernel(c_ref, r_ref, n_ref, o_ref):
    c = c_ref[...].astype(jnp.float32)            # (1, BL, BP)
    r = r_ref[...].astype(jnp.float32)            # (1, BL, 1)
    cn = n_ref[...].astype(jnp.float32)           # (1, 1, BP)
    o_ref[...] = (c + r * cn >= 1.0).astype(jnp.float32)


def dpc_screen_folds_pallas(C: jnp.ndarray, radii: jnp.ndarray,
                            col_norms_f: jnp.ndarray, *,
                            block_l: int = SUBLANE, block_p: int | None = None,
                            interpret: bool = False):
    """Fused Theorem-22 grid rule on the fold-stacked CV layout.

    ``C``: (K, L, p) stacked correlations (fold-k centers against the shared
    design), ``radii``: (K, L) safety-inflated ball radii, ``col_norms_f``:
    (K, p) per-fold masked column norms.  Returns ``feat_keep (K, L, p)``
    bool — one streaming pass instead of materialising omega in HBM.  The
    grid walks (fold, lambda-tile, feature-tile); radii ride as a
    ``(K, L, 1)`` column and the norms as a ``(K, 1, p)`` row, so every
    block's minor two dims are (8k or full, 128k or full)."""
    K, L, p = C.shape
    if radii.shape != (K, L) or col_norms_f.shape != (K, p):
        raise ValueError(f"expected radii (K, L) and col_norms_f (K, p) for "
                         f"C {C.shape}; got {radii.shape} / "
                         f"{col_norms_f.shape}")
    bl = _row_block(L, block_l)
    bp = block_p if block_p is not None else _lane_block(p, bl * 4)
    keep = pl.pallas_call(
        _dpc_screen_folds_kernel,
        grid=(K, pl.cdiv(L, bl), pl.cdiv(p, bp)),
        in_specs=[
            pl.BlockSpec((1, bl, bp), lambda k, i, j: (k, i, j)),
            pl.BlockSpec((1, bl, 1), lambda k, i, j: (k, i, I0)),
            pl.BlockSpec((1, 1, bp), lambda k, i, j: (k, I0, j)),
        ],
        out_specs=pl.BlockSpec((1, bl, bp), lambda k, i, j: (k, i, j)),
        out_shape=jax.ShapeDtypeStruct((K, L, p), jnp.float32),
        interpret=interpret,
    )(C, radii[:, :, None], col_norms_f[:, None, :])
    return keep > 0.5
