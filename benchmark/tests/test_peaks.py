import pytest

from layers import peaks


def test_v5e_peaks_by_the_kind_jax_reports():
    p = peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops_per_s"] == 393e12


@pytest.mark.parametrize("kind", ["v5e", "TPU v4", "cpu", ""])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks(kind)
