import pytest

from bench.peaks import peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks(kind)
