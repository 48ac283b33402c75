"""The table of peaks and the bytes of a lookup."""
import pytest

from bench import roofline


def test_known_device_has_its_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12


def test_unknown_device_kind_raises():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("cpu")


def test_lookup_bytes_follow_the_plane_widths():
    cfg = {"num_slots": 14, "use_fingerprints": True}
    # fingerprints 14 B, key halves and value 3 x 56 B, meta word 4 B
    assert roofline.lookup_row_bytes(cfg) == 186
    assert roofline.lookup_bytes(cfg) == 372
