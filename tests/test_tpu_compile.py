"""The Pallas kernels, and the engine's per-group spectral norms, compile
for a TPU v5e at the paper's real sizes.

Interpret mode (every other kernel test) never applies the TPU's block
tiling rule or its memory limits; Mosaic does, when it compiles for a chip.
These tests compile each kernel wrapper for one chip of a *described*
``v5e:2x2`` topology (no chip attached) at the shapes of the main path:

* ADNI (paper Table 2): N=747, p=426,040 SNPs in G=94,765 gene groups of
  at most n_max=10 features;
* the nonnegative-Lasso image dictionary of Table 3: p=11,553 columns;
* the grid screens' L=128 lambdas and the CV engine's K=5 folds.

The topology is described inside a module-scoped fixture (never at import,
so every xdist worker collects the same tests and only the worker given
this file loads the TPU compiler), and the tests skip where it cannot be
described.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

ADNI_N, ADNI_P, ADNI_G, ADNI_NMAX = 747, 426_040, 94_765, 10
DPC_P = 11_553
V5E_HBM = 15.75 * 2 ** 30        # bytes a v5e program may hold


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(*dims, dtype=f32)``: an abstract operand on one v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    """Compile for the described chip (compiled, never interpret mode) and
    return (device bytes the program holds, bytes of its arguments)."""
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert held < V5E_HBM, f"{held / 2 ** 30:.2f} GiB does not fit a v5e"
    return held, m.argument_size_in_bytes


def test_xtv_compiles_at_adni(shape):
    held, _ = _compile(ops.xtv, shape(ADNI_N, ADNI_P), shape(ADNI_N))
    # X is streamed as it lies: nothing beyond X, v and the output
    assert held <= 4 * (ADNI_N * ADNI_P + ADNI_N + ADNI_P) * 1.01


def test_screen_norms_batched_compiles_at_adni(shape):
    L = 128
    held, arg = _compile(ops.screen_norms_batched,
                         shape(ADNI_NMAX, L, ADNI_G),
                         shape(ADNI_NMAX, ADNI_G, dtype=jnp.bool_))
    # slot-major layout: n_max * G slots per row, never n_max padded to
    # 128 lanes (which made the group-major layout ~13x the bytes of C)
    c_bytes = 4 * L * ADNI_P
    assert arg <= 1.01 * 4 * ADNI_NMAX * L * ADNI_G
    assert arg <= 2.5 * c_bytes
    assert held <= 2 * arg + 2 * 4 * L * ADNI_G


def test_screen_norms_folds_compiles_at_adni(shape):
    K, L = 5, 32
    held, arg = _compile(ops.screen_norms_folds,
                         shape(ADNI_NMAX, K, L, ADNI_G),
                         shape(ADNI_NMAX, ADNI_G, dtype=jnp.bool_))
    assert arg <= 2.5 * 4 * K * L * ADNI_P
    assert held <= 2 * arg + 2 * 4 * K * L * ADNI_G


def test_sgl_prox_padded_compiles_at_adni(shape):
    held, _ = _compile(ops.sgl_prox_padded,
                       shape(ADNI_NMAX, ADNI_G),
                       shape(ADNI_NMAX, ADNI_G, dtype=jnp.bool_),
                       shape(), shape(ADNI_G))
    assert held <= 4 * 4 * ADNI_NMAX * ADNI_G


@pytest.mark.parametrize("L", [1, 20, 128])
def test_dpc_screen_folds_compiles_at_table3(shape, L):
    K = 5
    held, _ = _compile(ops.dpc_screen_folds, shape(K, L, DPC_P),
                       shape(K, L), shape(K, DPC_P))
    assert held <= 3 * 4 * K * L * DPC_P + np.prod((K, DPC_P)) * 4 * 2


def test_group_spectral_norms_compile_at_adni(topo):
    """The banded Gram route fuses its shifted products into reductions
    over X on the chip's compiler too: its temporaries stay a few percent
    of X's 1.27 GB, where one copy of X would raise ``peak_hbm_gb``."""
    from jax.sharding import SingleDeviceSharding
    from repro.core import GroupSpec, group_spectral_norms
    from repro.core.linalg import gram_groups
    one_chip = SingleDeviceSharding(topo.devices[0])
    sizes = np.full(ADNI_G, 4)
    sizes[:23_490] = 6                   # 426,040 columns, ragged
    sizes[0], sizes[1] = ADNI_NMAX, 2
    spec = GroupSpec.from_sizes(sizes)
    assert spec.num_features == ADNI_P
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        spec)
    X = jax.ShapeDtypeStruct((ADNI_N, ADNI_P), jnp.float32,
                             sharding=one_chip)
    assert gram_groups(X, spec) == ADNI_G
    m = group_spectral_norms.lower(X, spec).compile().memory_analysis()
    assert m.temp_size_in_bytes < 0.05 * 4 * ADNI_N * ADNI_P
