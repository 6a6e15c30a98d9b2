"""Per-group spectral norms: the banded Gram route of
``group_spectral_norms`` against the per-group power iteration it stands in
for (which wider groups still take), against numpy's exact ``||X_g||_2``,
and its memory next to X's."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import GroupSpec, group_spectral_norms
from repro.core import linalg

ITERS = 30
# relative agreement of the two routes (the same iterates, rounded apart)
AGREE = {np.float32: 1e-4, np.float64: 1e-10}
# how far a power-iteration estimate may pass the exact norm (rounding only)
OVER = {np.float32: 1e-5, np.float64: 1e-12}


def _sizes(case, rng):
    if case == "ragged_singletons":       # many size-1 groups among 1..8
        s = rng.integers(1, 9, 120)
        s[::3] = 1
        return s
    if case == "last_group_clamped":      # last starts within n_max of p
        return np.concatenate([rng.integers(1, 9, 80), [8, 2, 1]])
    if case == "uniform":
        return np.full(60, 5)
    if case == "wide":                    # two groups above GRAM_MAX_SIZE
        return np.concatenate([rng.integers(1, 9, 20),
                               [linalg.GRAM_MAX_SIZE + 7, 3,
                                linalg.GRAM_MAX_SIZE + 1],
                               rng.integers(1, linalg.GRAM_MAX_SIZE + 1, 20)])
    if case == "uniform_wide":            # every group above GRAM_MAX_SIZE
        return np.full(4, linalg.GRAM_MAX_SIZE + 3)
    if case == "singletons_and_one_wide":  # blocks capped well below 32
        return np.concatenate([np.ones(300, int), [40]])
    raise ValueError(case)


def _problem(case, dtype, N=1024, seed=0):
    rng = np.random.default_rng(seed)
    sizes = _sizes(case, rng)
    spec = GroupSpec.from_sizes(sizes)
    X = rng.standard_normal((N, int(sizes.sum())))
    # correlated neighbours, so the Gram blocks are far from diagonal
    X[:, 1:] += 0.7 * X[:, :-1]
    return X.astype(dtype), spec


def _exact(X, spec):
    X = np.asarray(X, np.float64)
    return np.array([np.linalg.norm(X[:, s:s + n], 2)
                     for s, n in zip(np.asarray(spec.starts),
                                     np.asarray(spec.sizes))])


@jax.jit
def _power(X, spec):
    """The per-group power iteration on X's columns, for every group."""
    return jax.vmap(lambda s, n: linalg._block_power(
        X, s, n, spec.max_size, ITERS))(spec.starts, spec.sizes)


# groups a route takes off Gram blocks: all, all but the wide ones, none
GRAM_GROUPS = {"wide": lambda spec: spec.num_groups - 2,
               "singletons_and_one_wide": lambda spec: spec.num_groups - 1,
               "uniform_wide": lambda spec: 0}


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("case", ["ragged_singletons", "last_group_clamped",
                                  "uniform", "wide", "uniform_wide",
                                  "singletons_and_one_wide"])
def test_gram_route_matches_the_power_iteration(case, dtype):
    X, spec = _problem(case, dtype)
    n_gram = GRAM_GROUPS.get(case, lambda spec: spec.num_groups)(spec)
    assert linalg.gram_groups(X, spec) == n_gram
    got = np.asarray(group_spectral_norms(jnp.asarray(X), spec, iters=ITERS))
    ref = np.asarray(_power(jnp.asarray(X), spec))
    assert got.dtype == ref.dtype == dtype
    np.testing.assert_allclose(got, ref, rtol=AGREE[dtype], atol=0)
    exact = _exact(X, spec)
    # power iteration from a start inside the group: never above the norm
    assert np.all(got <= exact * (1 + OVER[dtype]))
    assert np.all(got > 0)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_gram_route_on_a_bucketed_spec(dtype):
    """Zero-size groups and a garbage bin wider than n_max, over zero
    padding columns, as ``GroupSpec.bucketed_subset`` lays them out."""
    X, spec = _problem("ragged_singletons", dtype)
    keep = np.random.default_rng(1).random(X.shape[1]) < 0.4
    sub, cols = spec.bucketed_subset(keep, p_bucket=int(keep.sum()) + 20,
                                     g_bucket=spec.num_groups)
    Xb = np.zeros((X.shape[0], sub.num_features), dtype)
    Xb[:, :len(cols)] = X[:, cols]
    got = np.asarray(group_spectral_norms(jnp.asarray(Xb), sub))
    ref = np.asarray(_power(jnp.asarray(Xb), sub))
    np.testing.assert_allclose(got, ref, rtol=AGREE[dtype], atol=0)
    assert np.all(got[np.asarray(sub.sizes) == 0] == 0)
    assert got[-1] == 0                    # the garbage bin's zero columns


@pytest.mark.parametrize("wide", [False, True])
def test_gram_route_makes_no_copy_of_x(wide):
    """Compiled at ADNI's N with ~20,000 columns in ragged groups of at most
    8: the route's temporaries (shifted products, Gram blocks, iterates) are
    a few percent of X; a padded, shifted or transposed copy of X alone
    would be 100%.  One wider group raises the blocks' slots towards
    ``GRAM_MAX_SIZE``, and they stay within ``GRAM_MAX_SHARE`` of X."""
    N = 747
    rng = np.random.default_rng(2)
    sizes = rng.integers(1, 9, 4_450)
    if wide:
        sizes[7] = linalg.GRAM_MAX_SIZE + 8
    spec = GroupSpec.from_sizes(sizes)
    X = jax.ShapeDtypeStruct((N, spec.num_features), jnp.float32)
    assert linalg.gram_groups(X, spec) == spec.num_groups - wide
    m = group_spectral_norms.lower(X, spec).compile().memory_analysis()
    x_bytes = 4 * N * spec.num_features
    limit = 0.10 + (linalg.GRAM_MAX_SHARE if wide else 0.0)
    assert m.temp_size_in_bytes < limit * x_bytes
