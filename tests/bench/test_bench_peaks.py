"""The table of published peaks is keyed by device kind, and a device it
does not know is an error, never a default."""
import pytest

from bench import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks(kind)
