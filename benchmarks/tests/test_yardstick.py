"""(c) the plausibility guard and the unknown-``device_kind`` error."""

import pytest

import yardstick


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(yardstick.UnknownDevice, match="TPU v9 imaginary"):
        yardstick.peaks_for("TPU v9 imaginary")
    with pytest.raises(yardstick.UnknownDevice):
        yardstick.peaks_for("cpu")


def test_v5e_row_is_the_published_one():
    peaks = yardstick.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9
    assert "Google Cloud" in peaks["source"]


def test_a_rate_above_the_peak_is_refused():
    peaks = yardstick.peaks_for("TPU v5 lite")
    flops = 6 * 89_737_226  # mlp-deep's matmul FLOPs per sample, roughly
    at_peak = peaks["flops_per_s"] / flops
    assert yardstick.check_plausible(0.9 * at_peak, flops, 1, peaks) == pytest.approx(0.9)
    with pytest.raises(yardstick.ImplausibleRate, match="did not cover"):
        yardstick.check_plausible(1.1 * at_peak, flops, 1, peaks)
    # four chips may do four times as much
    assert yardstick.check_plausible(3.9 * at_peak, flops, 4, peaks) < 1
    with pytest.raises(yardstick.ImplausibleRate):
        yardstick.check_plausible(4.1 * at_peak, flops, 4, peaks)
