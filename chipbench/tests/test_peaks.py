import json

import pytest

from chipbench import peaks


def test_v5e_entry_has_its_published_peaks():
    p = peaks.peak_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for(kind)


def test_every_entry_names_its_source(tmp_path):
    with open(peaks.PEAKS_FILE) as f:
        table = json.load(f)
    for kind, entry in table.items():
        assert entry["source"], kind
        assert entry["hbm_bytes_per_s"] > 0 and entry["bf16_flops_per_s"] > 0
