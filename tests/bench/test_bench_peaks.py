"""The peak table: known kinds, and refusal of an unknown one."""

import pytest

from bench.peaks import UnknownDevice, peaks_for


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)
