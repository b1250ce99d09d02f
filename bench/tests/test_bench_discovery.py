"""Cells, configurations, traffic mixes and metrics are found by name:
each is added as a file, and no existing file changes."""
import json
import shutil
from pathlib import Path

import pytest

from bench import spec
from bench.tests import tiny

ROOT = Path(spec.ROOT)


def test_every_listed_cell_resolves():
    bm = spec.benchmark()
    for w in bm["workloads"]:
        c = spec.cell(w["name"])
        assert (c["config"], c["traffic"], c["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert c["why"] == w["why"]
        conf = spec.config(c["config"])
        spec.model_config(conf)                  # widths match the program
        spec.traffic(c["traffic"])
        assert spec.metrics_for(bm, w["name"], False)
        assert spec.metrics_for(bm, w["name"], True)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for c in bm["configs"]:
        conf = spec.read_json(ROOT / c["file"])
        assert conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]


def test_config_widths_are_checked(tmp_path):
    bad = dict(tiny.GPT2, n_embd=65)
    with pytest.raises(ValueError, match="d_model"):
        spec.model_config(bad)


def test_new_files_are_found_without_edits(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    conf = dict(tiny.GPT2)
    conf.pop("name")
    (bench / "configs" / "new-model.json").write_text(json.dumps(conf))
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(tiny.MIX))
    cell = tiny.cell(config="new-model", traffic="new_mix")
    cell.pop("name")
    (bench / "workloads" / "new-cell.json").write_text(json.dumps(cell))
    (bench / "metrics" / "tokens.total.py").write_text(
        "def read(v):\n    return float(len(v.window.served))\n")
    bm = spec.benchmark()
    bm["per_layer"].append({"name": "tokens.total", "unit": "requests",
                            "better": "higher", "source": "host_clock",
                            "layer": "front-end", "moves": "output_tok_s",
                            "workloads": ["new-cell"]})
    c = spec.cell("new-cell", bench)
    assert spec.config(c["config"], bench)["n_embd"] == 64
    assert spec.traffic(c["traffic"], bench)["body"]["median"] == 12
    names = [m["name"] for m in spec.metrics_for(bm, "new-cell", True)]
    assert "tokens.total" in names
    assert "tokens.total" not in [
        m["name"] for m in spec.metrics_for(bm, "gpt2s-chat-1c", True)]

    class V:
        class window:
            served = [1, 2, 3]
    assert spec.reader("tokens.total", bench)(V) == 3.0
    after = {p: p.read_bytes() for p in before}
    assert after == before
