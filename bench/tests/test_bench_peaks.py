import pytest

from bench import peaks


def test_v5e_peaks_and_source():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v4")
    with pytest.raises(KeyError):
        peaks.peak("cpu")
