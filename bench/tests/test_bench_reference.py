"""The benchmark's f64 reference against the smoke test's, on tiny
problems: solutions of several qualities, SGL and nonnegative Lasso."""
import numpy as np
import pytest

from bench import reference
from bench.data import group_sizes

chip_smoke = pytest.importorskip("chip_smoke")


def _sgl_case(seed):
    rng = np.random.default_rng(seed)
    sizes = group_sizes(120, 30, 8, rng)
    X = rng.standard_normal((40, 120)).astype(np.float32)
    y = rng.standard_normal(40)
    lams = np.array([30.0, 12.0, 6.0, 3.0])
    B = rng.standard_normal((4, 120)) * (rng.random((4, 120)) < 0.1)
    return X, y, sizes, lams, B


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sgl_gaps_match_the_smoke_reference(seed):
    X, y, sizes, lams, B = _sgl_case(seed)
    X64 = X.astype(np.float64)
    ref = chip_smoke.sgl_gaps(X64, y, sizes, 0.7, lams, B)
    got = reference.gap_ratios(X, np.broadcast_to(y, (4, 40)), lams, B,
                               1e-5, sizes=sizes, alpha=0.7, block=32)
    scale = 1e-5 * 0.5 * float(y @ y)
    np.testing.assert_allclose(got * scale, ref, rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("seed", [0, 1])
def test_nn_gaps_match_the_smoke_reference(seed):
    rng = np.random.default_rng(seed)
    X = rng.random((30, 90)).astype(np.float32)
    y = rng.random(30)
    lams = np.array([2.0, 1.0, 0.5])
    B = np.abs(rng.standard_normal((3, 90))) * (rng.random((3, 90)) < 0.1)
    ref = chip_smoke.nn_gaps(X.astype(np.float64), y, lams, B)
    got = reference.gap_ratios(X, np.broadcast_to(y, (3, 30)), lams, B,
                               1e-5, block=16)
    np.testing.assert_allclose(got * 1e-5 * 0.5 * float(y @ y), ref,
                               rtol=1e-9, atol=1e-9)


def test_negative_or_nonfinite_rows_read_infinite():
    rng = np.random.default_rng(3)
    X = rng.random((20, 50)).astype(np.float32)
    y = rng.random(20)
    B = np.zeros((3, 50))
    B[1, 4] = -0.1
    B[2, 7] = np.nan
    got = reference.gap_ratios(X, np.broadcast_to(y, (3, 20)),
                               np.array([1.0, 1.0, 1.0]), B, 1e-5)
    assert np.isfinite(got[0]) and np.isinf(got[1]) and np.isinf(got[2])


def test_optimal_zero_row_at_lambda_max_has_no_gap():
    X, y, sizes, _, _ = _sgl_case(4)
    # with alpha = 0 the penalty is the plain l1, so lambda_max = max|x^T y|
    lam = np.array([np.abs(X.astype(np.float64).T @ y).max()])
    got = reference.gap_ratios(X, y[None, :], lam, np.zeros((1, 120)),
                               1e-5, sizes=sizes, alpha=0.0)
    assert abs(got[0]) < 1e-6
