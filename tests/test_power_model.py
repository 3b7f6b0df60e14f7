"""Published chip peaks, looked up by the device kind JAX reports."""

import pytest

from repro.core.power_model import DEVICE_PEAKS, TPU_V5E, hardware_for


def test_peaks_by_device_kind():
    assert hardware_for("TPU v5 lite") is TPU_V5E
    assert TPU_V5E.peak_flops_bf16 == 197e12
    assert TPU_V5E.hbm_bandwidth == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        hardware_for("cpu")
    assert "cpu" not in DEVICE_PEAKS
