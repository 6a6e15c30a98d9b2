"""Per-kernel allclose tests: Pallas (interpret=True on CPU) vs pure-jnp
oracle, swept over shapes and dtypes (seeded sweeps + parametrised edges)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from conftest import rand_cases

from repro.kernels import ops, ref
from repro.kernels.xtv import xtv_pallas
from repro.kernels.screen_norms import screen_norms_pallas
from repro.kernels.sgl_prox import sgl_prox_pallas


DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("N,p", [(7, 13), (128, 512), (300, 1000), (512, 512)])
def test_xtv_shapes(N, p, dt):
    rng = np.random.default_rng(N * p)
    X = jnp.asarray(rng.standard_normal((N, p)), dt)
    v = jnp.asarray(rng.standard_normal(N), dt)
    out = xtv_pallas(X, v, interpret=True)
    expect = ref.xtv_ref(X, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), **_tol(dt))


@pytest.mark.parametrize("N,p,seed", rand_cases(
    15, ("int", 1, 200), ("int", 1, 300), ("int", 0, 10**6), seed=11))
def test_xtv_sweep(N, p, seed):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((N, p)), jnp.float32)
    v = jnp.asarray(rng.standard_normal(N), jnp.float32)
    out = xtv_pallas(X, v, interpret=True, block_n=128, block_p=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.xtv_ref(X, v)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("G,nm", [(1, 1), (5, 17), (100, 64), (257, 130)])
def test_screen_norms_shapes(G, nm, dt):
    rng = np.random.default_rng(G * nm)
    c = jnp.asarray(rng.standard_normal((nm, G)) * 2, dt)
    m = jnp.asarray(rng.random((nm, G)) > 0.25)
    s, i = screen_norms_pallas(c[:, None, :], m, interpret=True)
    s, i = s[0], i[0]
    sr, ir = ref.screen_norms_ref(c, m)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), **_tol(dt))
    np.testing.assert_allclose(np.asarray(i), np.asarray(ir), **_tol(dt))


@pytest.mark.parametrize("G,nm,seed,t_l1", rand_cases(
    15, ("int", 1, 80), ("int", 1, 70), ("int", 0, 10**6),
    ("float", 0.0, 3.0), seed=12))
def test_sgl_prox_sweep(G, nm, seed, t_l1):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.standard_normal((nm, G)) * 3, jnp.float32)
    m = jnp.asarray(rng.random((nm, G)) > 0.3)
    tg = jnp.asarray(np.abs(rng.standard_normal(G)), jnp.float32)
    out = sgl_prox_pallas(v, m, t_l1, tg, interpret=True, block_g=32)
    expect = ref.sgl_prox_ref(v, m, jnp.float32(t_l1), tg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_kernels_match_core_library():
    """The fused kernels implement exactly the core-library semantics used by
    tlfre_screen + sgl_prox (integration contract)."""
    from repro.core import GroupSpec, shrink, group_norms, group_max_abs, sgl_prox
    from repro.core.groups import pad_slots
    rng = np.random.default_rng(0)
    spec = GroupSpec.from_sizes(rng.integers(1, 9, size=40))
    p = spec.num_features
    c = jnp.asarray(rng.standard_normal(p) * 2)
    c_slots = pad_slots(spec, c[None].astype(jnp.float32))   # (n_max, 1, G)
    s2, cinf = screen_norms_pallas(c_slots, spec.pad_mask.T, interpret=True)
    s2, cinf = s2[0], cinf[0]
    np.testing.assert_allclose(
        np.asarray(jnp.sqrt(s2)),
        np.asarray(group_norms(spec, shrink(c))).astype(np.float32), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(cinf),
        np.asarray(group_max_abs(spec, c)).astype(np.float32), rtol=1e-6)

    t_l1, t_g = 0.3, jnp.asarray(0.2 * np.asarray(spec.weights))
    out_slots = sgl_prox_pallas(pad_slots(spec, c.astype(jnp.float32)),
                                spec.pad_mask.T, t_l1,
                                t_g.astype(jnp.float32), interpret=True)
    expect = sgl_prox(spec, c, t_l1, t_g)
    got = np.asarray(out_slots).T[np.asarray(spec.pad_mask)]
    np.testing.assert_allclose(got, np.asarray(expect), rtol=1e-5, atol=1e-6)


def test_ops_jit_wrappers():
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    v = jnp.asarray(rng.standard_normal(64), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.xtv(X, v)),
                               np.asarray(ref.xtv_ref(X, v)), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Ragged, non-multiple-of-128 layouts: padded-lane masking must be exact
# ---------------------------------------------------------------------------

RAGGED_SIZES = [
    [1, 3, 130, 7, 129, 2, 64, 200, 5, 31],   # n_max = 200 (not 128k)
    [127, 1, 1, 1, 255],                       # n_max = 255
    [5] * 37 + [133],                          # one oversized straggler
]


def _ragged_layout(sizes, seed):
    """Slot-major (n_max, G) layout for a ragged GroupSpec with GARBAGE in
    the invalid slots — the kernels must mask them, not read them.  Returns
    (spec, slot mask (n_max, G), clean, dirty)."""
    from repro.core import GroupSpec
    from repro.core.groups import pad_slots
    spec = GroupSpec.from_sizes(sizes)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(spec.num_features) * 2, jnp.float32)
    mask = spec.pad_mask.T
    clean = jnp.where(mask, pad_slots(spec, x), 0.0)
    garbage = jnp.asarray(
        rng.standard_normal(clean.shape) * 1e6, jnp.float32)
    dirty = jnp.where(mask, clean, garbage)
    return spec, mask, clean, dirty


@pytest.mark.pallas
@pytest.mark.parametrize("sizes", RAGGED_SIZES)
def test_screen_norms_ragged_masks_padded_lanes(sizes):
    spec, mask, clean, dirty = _ragged_layout(sizes, seed=sum(sizes))
    s, i = screen_norms_pallas(dirty[:, None, :], mask, interpret=True,
                               block_g=32)
    sr, ir = ref.screen_norms_ref(clean, mask)
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(sr), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(i[0]), np.asarray(ir), rtol=1e-6)


@pytest.mark.pallas
@pytest.mark.parametrize("sizes", RAGGED_SIZES)
def test_sgl_prox_ragged_masks_padded_lanes(sizes):
    spec, mask, clean, dirty = _ragged_layout(sizes, seed=len(sizes))
    t_l1 = 0.4
    tg = jnp.asarray(0.3 * np.asarray(spec.weights), jnp.float32)
    out = sgl_prox_pallas(dirty, mask, t_l1, tg, interpret=True,
                          block_g=32)
    expect = ref.sgl_prox_ref(clean, mask, jnp.float32(t_l1), tg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    # padded slots must come out exactly zero (the engine scatters them back)
    assert float(jnp.max(jnp.abs(jnp.where(mask, 0.0, out)))) == 0.0


@pytest.mark.pallas
def test_screen_norms_batched_grid_layout():
    """The (n_max, L, G) grid layout used by the batched path engine."""
    spec, mask, clean, dirty = _ragged_layout(RAGGED_SIZES[0], seed=0)
    rng = np.random.default_rng(1)
    L = 5
    scales = jnp.asarray(rng.uniform(0.2, 3.0, L), jnp.float32)
    grid_dirty = scales[None, :, None] * dirty[:, None, :]
    s, i = ops.screen_norms_batched(grid_dirty, mask)
    for r in range(L):
        sr, ir = ref.screen_norms_ref(scales[r] * clean, mask)
        np.testing.assert_allclose(np.asarray(s[r]), np.asarray(sr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(i[r]), np.asarray(ir),
                                   rtol=1e-5)


@pytest.mark.pallas
@pytest.mark.parametrize("sizes", RAGGED_SIZES)
def test_screen_norms_folds_matches_per_row_kernel(sizes):
    """The (n_max, K, L, G) fold-stack layout of the CV engine: every
    (fold, lambda) slice must match the single-row oracle, garbage in the
    padded slots masked."""
    spec, mask, clean, dirty = _ragged_layout(sizes, seed=sum(sizes) + 1)
    rng = np.random.default_rng(2)
    K, L = 3, 4
    scales = jnp.asarray(rng.uniform(0.2, 3.0, (K, L)), jnp.float32)
    stack_dirty = scales[None, :, :, None] * dirty[:, None, None, :]
    s, i = ops.screen_norms_folds(stack_dirty, mask)
    assert s.shape == (K, L, spec.num_groups)
    for k in range(K):
        for r in range(L):
            sr, ir = ref.screen_norms_ref(scales[k, r] * clean, mask)
            np.testing.assert_allclose(np.asarray(s[k, r]), np.asarray(sr),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(np.asarray(i[k, r]), np.asarray(ir),
                                       rtol=1e-5)


@pytest.mark.pallas
def test_dpc_screen_folds_matches_jnp_oracle():
    """The fused fold-stack DPC threshold: exact agreement with the
    unfused omega >= 1 rule on a ragged non-multiple-of-128 p."""
    rng = np.random.default_rng(4)
    K, L, p = 3, 5, 333
    C = jnp.asarray(rng.standard_normal((K, L, p)) * 0.8, jnp.float32)
    radii = jnp.asarray(np.abs(rng.standard_normal((K, L))), jnp.float32)
    col_n = jnp.asarray(np.abs(rng.standard_normal((K, p))) + 0.1,
                        jnp.float32)
    keep = ops.dpc_screen_folds(C, radii, col_n)
    expect = (C + radii[:, :, None] * col_n[:, None, :]) >= 1.0
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(expect))
