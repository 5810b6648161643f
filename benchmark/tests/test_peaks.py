"""The peak table: v5e's published peaks, keyed by device kind, and an
unknown device is an error."""

import json

import pytest

from benchmark import spec


def test_peaks_table_and_unknown_device(tmp_path):
    p = spec.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no entry"):
        spec.peaks("TPU v9 imaginary")
    (tmp_path / "benchmark").mkdir()
    (tmp_path / "benchmark" / "peaks.json").write_text(json.dumps({}))
    with pytest.raises(KeyError):
        spec.peaks("TPU v5 lite", root=tmp_path)
