"""Operations and bytes from shapes, peaks by device kind, roofline share."""
import pytest

from bench import costs


def test_xtv_counts_one_pass_over_x():
    # X (3, 5) f32: 15 elements read, v (3) read, out (5) written
    assert costs.xtv(3, 5) == (2 * 15, 4 * (15 + 3 + 5))
    assert costs.xtv(747, 426_040) == (2 * 747 * 426_040,
                                       4 * (747 * 426_040 + 747 + 426_040))


def test_dpc_screen_counts_a_one_byte_mask():
    # C (2, 3, 4) f32 read, radii (2, 3) f32, norms (2, 4) f32, mask 24 B
    assert costs.dpc_screen(2, 3, 4) == (3 * 24, 4 * (24 + 6 + 8) + 24)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        costs.peaks("TPU v99 imaginary")


def test_v5e_peaks_and_roofline_share():
    peak = costs.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    # 819 MB in 2 ms is half of the bandwidth roofline
    assert costs.roofline_share(1.0, 819e6, 2e-3, peak) == pytest.approx(50)
    # compute-bound work: 197 GFLOP in 1 s is 0.1% of peak
    assert costs.roofline_share(197e9, 0.0, 1.0, peak) == pytest.approx(0.1)
