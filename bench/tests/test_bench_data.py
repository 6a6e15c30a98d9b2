"""The cells' inputs: group layout, determinism in the seed."""
import numpy as np
import pytest

from bench import catalog, data


@pytest.mark.parametrize("seed", [0, 2**31 + 7, -3])
def test_gwas_groups_have_the_papers_count_and_total(seed):
    cfg = catalog.config("gwas_adni")
    sizes = data.group_sizes(cfg["n_features"], cfg["n_groups"],
                             cfg["max_group_size"], data.rng_for(seed, 0))
    assert len(sizes) == 94_765 and int(sizes.sum()) == 426_040
    assert sizes.min() >= 1 and sizes.max() == 8


def test_impossible_group_layout_is_refused():
    with pytest.raises(ValueError):
        data.group_sizes(100, 10, 8, data.rng_for(0, 0))


def _arrays(ys):
    return [np.asarray(y) for y in ys]


@pytest.mark.parametrize("name", ["gwas_adni", "imgdict_dpc"])
def test_seed_orders_one_pool_on_one_design(name):
    """One design per configuration; the run's seed draws fresh responses,
    the warm-up and the window from streams of their own, each response
    the same however many are made at once."""
    cfg = dict(catalog.config(name), n_samples=24, n_features=64,
               n_groups=16)
    d, d2 = data.make_design(cfg), data.make_design(cfg)
    assert np.array_equal(np.asarray(d.X), np.asarray(d2.X))

    def ys(seed, stream, start=0, count=4):
        return _arrays(data.make_responses(cfg, d, seed, stream, start,
                                           count))

    a = ys(11, data.WINDOW)
    assert all(np.array_equal(u, v) for u, v in zip(a, ys(11, data.WINDOW)))
    assert all(np.array_equal(u, v)
               for u, v in zip(a[2:], ys(11, data.WINDOW, 2, 2)))
    assert not np.array_equal(a[0], a[1])
    warm = ys(11, data.WARMUP)
    other = ys(2**31 + 12, data.WINDOW)
    for y in a:
        assert not any(np.array_equal(y, v) for v in warm + other)
    other_design = data.make_design(dict(cfg, design_seed=1))
    assert not np.array_equal(np.asarray(d.X), np.asarray(other_design.X))


def test_gwas_response_follows_synthetic_1():
    cfg = dict(catalog.config("gwas_adni"), n_samples=24, n_features=400,
               n_groups=90, noise=0.0)
    d = data.make_design(cfg)
    gen = data.generator(cfg)
    idx, val = gen._active(cfg, d.sizes, data.rng_for(5, 2, 0))
    gid = np.repeat(np.arange(len(d.sizes)), d.sizes)
    groups, counts = np.unique(gid[idx], return_counts=True)
    assert len(groups) == int(0.1 * 90) and len(np.unique(idx)) == len(idx)
    assert np.all(counts == np.maximum(1, np.round(0.2 * d.sizes[groups])))
    y = data.make_responses(cfg, d, 5, 2, 0, 1)[0]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(d.X)[:, idx] @ val, rtol=1e-5,
                               atol=1e-5)


def test_unknown_generator_is_refused():
    with pytest.raises(ValueError, match="unknown generator"):
        data.make_design({"generator": "nope"})
