"""The peak table: the chip's published peaks, and an error for any other
device kind."""

from __future__ import annotations

import pytest

from harness.peaks import peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_ops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("cpu")
