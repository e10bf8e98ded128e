"""The Gram kernel's operation and byte counts, and the peak table."""
import pytest

from harness import kernel_cost, peaks


def test_gram_counts_from_unpadded_shapes():
    # 10 patterns of width 11 against themselves: norms 2*(10+10)*11,
    # cross products 2*10*10*11, 8 per entry; f32 reads and one write
    assert kernel_cost.gram(10, 10, 11) == (440 + 2200 + 800, 4 * (110 + 110 + 100))
    assert kernel_cost.gram(1, 10, 11) == (242 + 220 + 80, 4 * (11 + 110 + 10))


def test_gp_row_makes_one_call_per_step_posterior_and_forecast_step():
    gp = {"max_patterns": 10, "history": 10, "opt_steps": 10}
    shapes = kernel_cost.gp_forecast_grams(gp, 3)
    assert shapes == [(10, 10, 11)] * 11 + [(1, 10, 11)] * 3
    ops, nbytes = kernel_cost.gp_row_cost(gp, 3)
    assert ops == 11 * 3440 + 3 * 542
    assert nbytes == 11 * 1280 + 3 * 524


def test_least_time_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    t, bound = kernel_cost.least_seconds(3440, 1280, pk)
    assert bound == "memory" and t == pytest.approx(1280 / 819e9)
    t, bound = kernel_cost.least_seconds(1e12, 1.0, pk)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)


def test_v5e_peaks_and_unknown_device():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v6 lite", "_source"):
        with pytest.raises(KeyError):
            peaks.peaks(kind)
