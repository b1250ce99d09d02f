"""The table of peaks is keyed by device kind and refuses others."""
import pytest

from bench import run, spec


def test_v5e_peaks_with_source():
    p = run.peaks_for("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_kind_is_refused(kind):
    with pytest.raises(KeyError, match="no peaks"):
        run.peaks_for(kind)


def test_every_entry_names_its_source():
    table = spec.read_json(spec.BENCH / "peaks.json")
    assert table
    for kind, entry in table.items():
        assert entry["source"], kind
