"""Layer 2b: Pallas kernel audit — BlockSpec/grid contracts, ragged-tail
mask coverage, and the f64 gate, all provable without a TPU.

Checks over every kernel in ``repro.kernels``:

  * ``pallas/tpu-tiling``          the minor two dims of every block must
    be multiples of (8, 128) or equal the whole array dim — the rule
    Mosaic enforces when it compiles for a TPU, which interpret mode never
    checks.
  * ``pallas/f64-aval``            no float64 aval may reach a kernel
    signature; the kernels are f32-only by contract
    (``_require_f32_for_pallas``) and f64 operands would be silently
    truncated on TPU.
  * ``pallas/mask-coverage``       semantic check: poison every padding
    slot with 1e30 and compare the interpret-mode kernel against the
    pure-jnp oracle (``kernels/ref.py``) on ragged, non-multiple-of-128
    shapes.  If a ragged-tail mask misses a slot, the poison propagates
    and the outputs diverge.  Interpret mode fills a block's overhang past
    its operand with NaN, so the same comparison proves that edge blocks
    only feed dropped outputs or masked lanes.
  * ``pallas/f64-gate``            the screening entry points must REFUSE
    ``use_pallas=True`` on f64 inputs (TypeError), not silently downcast.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .findings import Finding
from .jaxpr_lint import iter_eqns

_SUBLANE, _LANE = 8, 128


def _pallas_eqns(closed):
    for eqn, _ in iter_eqns(closed.jaxpr):
        if eqn.primitive.name == "pallas_call":
            yield eqn


def check_traceable(fn, *args, name: str) -> list:
    """Trace ``fn(*args)`` and audit every pallas_call's block mappings."""
    findings = []
    closed = jax.make_jaxpr(fn)(*args)
    found_any = False
    for eqn in _pallas_eqns(closed):
        found_any = True
        gm = eqn.params["grid_mapping"]
        for bm in gm.block_mappings:
            shape = tuple(bm.array_aval.shape)
            dtype = bm.array_aval.dtype
            block = tuple(getattr(b, "block_size", b)
                          for b in bm.block_shape)
            if np.dtype(dtype) == np.float64:
                findings.append(Finding(
                    "pallas/f64-aval", "error", name,
                    f"float64 aval {shape} reaches a pallas_call operand "
                    f"of {name}; kernels are f32-only by contract"))
            for dim, align in ((-1, _LANE), (-2, _SUBLANE)):
                if len(block) < -dim or not isinstance(block[dim], int):
                    continue
                b = block[dim]
                if b != shape[dim] and b % align != 0:
                    findings.append(Finding(
                        "pallas/tpu-tiling", "error", name,
                        f"block {block} of operand {shape} in {name}: dim "
                        f"{len(block) + dim} is {b}, neither a multiple of "
                        f"{align} nor the whole dim"))
    if not found_any:
        findings.append(Finding(
            "pallas/no-kernel", "warning", name,
            f"no pallas_call found in the trace of {name} (registry "
            f"drift: the wrapper no longer reaches a kernel)"))
    return findings


# ---------------------------------------------------------------------------
# Representative ragged shapes (every dim deliberately NOT a multiple of
# its tile) — the kernels must pad internally and mask the tails.
# ---------------------------------------------------------------------------

_POISON = 1e30


def _ragged_spec():
    from ..core.groups import GroupSpec
    return GroupSpec.from_sizes([3, 7, 1, 5, 4, 9, 2, 6])   # p=37, G=8


def _structural_cases():
    """(name, fn, args) — traced for block/grid/f64 audits."""
    from ..kernels import ops

    rng = np.random.default_rng(0)
    spec = _ragged_spec()
    G, n_max = spec.num_groups, int(np.max(np.asarray(spec.sizes)))
    mask = jnp.asarray(np.asarray(spec.pad_mask).T)
    f32 = jnp.float32
    X = jnp.asarray(rng.standard_normal((137, 37)), f32)
    v = jnp.asarray(rng.standard_normal(137), f32)
    c_pad = jnp.asarray(rng.standard_normal((n_max, G)), f32)
    c_grid = jnp.asarray(rng.standard_normal((n_max, 5, G)), f32)
    c_folds = jnp.asarray(rng.standard_normal((n_max, 3, 5, G)), f32)
    C = jnp.asarray(rng.standard_normal((2, 3, 37)), f32)
    radii = jnp.asarray(rng.random((2, 3)), f32)
    col_n = jnp.asarray(rng.random((2, 37)) + 0.5, f32)
    t_group = jnp.asarray(rng.random(G) + 0.1, f32)

    def w(fn):           # pin interpret mode so tracing works off-TPU
        return lambda *a: fn(*a, interpret=True)

    return [
        ("kernels.xtv", w(ops.xtv), (X, v)),
        ("kernels.screen_norms_batched", w(ops.screen_norms_batched),
         (c_grid, mask)),
        ("kernels.screen_norms_folds", w(ops.screen_norms_folds),
         (c_folds, mask)),
        ("kernels.dpc_screen_folds", w(ops.dpc_screen_folds),
         (C, radii, col_n)),
        ("kernels.sgl_prox_padded", w(ops.sgl_prox_padded),
         (c_pad, mask, jnp.float32(0.3), t_group)),
    ]


def _mask_coverage() -> list:
    """Poison padding slots; interpret-mode kernels must match the jnp
    oracles bit-for-tolerance on ragged shapes."""
    from ..kernels import ops, ref

    findings = []
    rng = np.random.default_rng(1)
    spec = _ragged_spec()
    G, n_max = spec.num_groups, int(np.max(np.asarray(spec.sizes)))
    mask_np = np.asarray(spec.pad_mask).T          # slot-major (n_max, G)
    mask = jnp.asarray(mask_np)

    def poisoned(shape, mask_b):
        a = rng.standard_normal(shape).astype(np.float32)
        return jnp.asarray(np.where(mask_b, a, _POISON))

    def compare(name, got, want, atol=1e-5):
        got, want = np.asarray(got), np.asarray(want)
        if not np.all(np.isfinite(got)) or not np.allclose(
                got, want, atol=atol, rtol=1e-5):
            findings.append(Finding(
                "pallas/mask-coverage", "error", name,
                f"poisoned-padding output of {name} diverges from the jnp "
                f"oracle (max|diff|="
                f"{np.max(np.abs(got - want)) if np.all(np.isfinite(got)) else np.inf:.3g})"
                f" — a ragged-tail mask is leaking padding lanes"))

    # screen_norms: oracle sees clean data (mask zeroes it), kernel sees
    # poison in the masked-out slots
    slot = mask_np[:, None, :]
    c_np = rng.standard_normal((n_max, 5, G)).astype(np.float32)
    c_clean = jnp.asarray(np.where(slot, c_np, 0.0))
    c_poison = jnp.asarray(np.where(slot, c_np, _POISON))
    want = ref.screen_norms_ref(c_clean, mask)
    got = ops.screen_norms_batched(c_poison, mask, interpret=True)
    compare("kernels.screen_norms_batched", got[0], want[0])
    compare("kernels.screen_norms_batched", got[1], want[1])

    cf_np = rng.standard_normal((n_max, 3, 5, G)).astype(np.float32)
    cf_clean = jnp.asarray(np.where(slot[:, None], cf_np, 0.0))
    cf_poison = jnp.asarray(np.where(slot[:, None], cf_np, _POISON))
    want0 = ref.screen_norms_ref(cf_clean, mask)
    got0 = ops.screen_norms_folds(cf_poison, mask, interpret=True)
    compare("kernels.screen_norms_folds", got0[0], want0[0])
    compare("kernels.screen_norms_folds", got0[1], want0[1])

    # dpc_screen_folds pads (L, p) internally — no caller-side poison
    # surface, but ragged (K, L, p)=(2, 3, 37) exercises the tail lanes
    C_np = rng.standard_normal((2, 3, 37)).astype(np.float32)
    radii = jnp.asarray(rng.random((2, 3)).astype(np.float32))
    col_n = jnp.asarray((rng.random((2, 37)) + 0.5).astype(np.float32))
    C = jnp.asarray(C_np)
    want1 = (C + radii[:, :, None] * col_n[:, None, :]) >= 1.0
    got1 = ops.dpc_screen_folds(C, radii, col_n, interpret=True)
    compare("kernels.dpc_screen_folds", got1, want1, atol=0)

    v_np = rng.standard_normal((n_max, G)).astype(np.float32)
    v_clean = jnp.asarray(np.where(mask_np, v_np, 0.0))
    v_poison = jnp.asarray(np.where(mask_np, v_np, _POISON))
    t_l1 = jnp.float32(0.3)
    t_g = jnp.asarray((rng.random(G) + 0.1).astype(np.float32))
    want2 = ref.sgl_prox_ref(v_clean, mask, t_l1, t_g)
    got2 = ops.sgl_prox_padded(v_poison, mask, t_l1, t_g, interpret=True)
    compare("kernels.sgl_prox_padded", got2, want2)

    # xtv streams X unpadded; ragged (137, 37) covers the overhanging
    # tail (NaN-filled in interpret mode)
    X_np = rng.standard_normal((137, 37)).astype(np.float32)
    vv = rng.standard_normal(137).astype(np.float32)
    want3 = ref.xtv_ref(jnp.asarray(X_np), jnp.asarray(vv))
    got3 = ops.xtv(jnp.asarray(X_np), jnp.asarray(vv), interpret=True)
    compare("kernels.xtv", got3, want3, atol=1e-4)
    return findings


def _f64_gate() -> list:
    """use_pallas=True + f64 inputs must raise TypeError at the screening
    entry points, not silently downcast."""
    from ..core import dpc as _dpc
    from ..core import screening as _scr
    from ..core.groups import GroupSpec

    findings = []
    rng = np.random.default_rng(2)
    spec = GroupSpec.from_sizes([3, 2, 5])
    f64 = jnp.float64
    X = jnp.asarray(rng.standard_normal((6, 10)), f64)
    y = jnp.asarray(rng.standard_normal(6), f64)
    lams = jnp.asarray([1.0, 0.5], f64)
    vecP = jnp.ones(10, f64)
    vecG = jnp.ones(3, f64)
    Y = jnp.stack([y, y])
    TB = jnp.stack([y, y])
    lamsK = jnp.stack([lams, lams])
    vecPK = jnp.ones((2, 10), f64)
    vecGK = jnp.ones((2, 3), f64)

    gates = [
        ("screening.tlfre_screen_grid",
         lambda: _scr.tlfre_screen_grid(X, y, spec, 0.9, lams, 1.0, y, y,
                                        vecP, vecG, use_pallas=True)),
        ("screening.tlfre_screen_grid_folds",
         lambda: _scr.tlfre_screen_grid_folds(X, Y, spec, 0.9, lamsK, TB,
                                              TB, vecPK, vecGK,
                                              use_pallas=True)),
        ("dpc.dpc_screen_grid_folds",
         lambda: _dpc.dpc_screen_grid_folds(X, Y, lamsK, TB, TB, vecPK,
                                            use_pallas=True)),
    ]
    for name, call in gates:
        try:
            jax.block_until_ready(call())
        except TypeError:
            continue               # the gate fired — contract holds
        except Exception as exc:   # pragma: no cover - diagnostic
            findings.append(Finding(
                "pallas/f64-gate", "error", name,
                f"{name} with use_pallas=True on float64 raised "
                f"{type(exc).__name__} instead of TypeError: {exc}"))
        else:
            findings.append(Finding(
                "pallas/f64-gate", "error", name,
                f"{name} accepted use_pallas=True on float64 inputs — "
                f"the f32-only kernel gate is broken"))
    return findings


def run() -> list:
    findings = []
    for name, fn, args in _structural_cases():
        findings.extend(check_traceable(fn, *args, name=name))
    findings.extend(_mask_coverage())
    findings.extend(_f64_gate())
    return findings
