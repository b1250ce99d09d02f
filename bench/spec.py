"""Find a cell's files by name.

``BENCHMARK.json`` at the checkout's root lists the cells and metrics;
``bench/workloads/<cell>.json`` holds how a cell runs,
``bench/configs/<config>.json`` its model, ``bench/traffic/<mix>.json``
its traffic, ``bench/references/<family>.py`` the plain reference the
model file names, and ``bench/metrics/<metric>.py`` each metric's
reader.  Adding any of these is adding a file; nothing here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return read_json(root / "BENCHMARK.json")


def cell(name: str, bench: Path = BENCH) -> dict:
    c = read_json(bench / "workloads" / f"{name}.json")
    c["name"] = name
    return c


def config(name: str, bench: Path = BENCH) -> dict:
    c = read_json(bench / "configs" / f"{name}.json")
    c["name"] = name
    return c


def traffic(name: str, bench: Path = BENCH) -> dict:
    return read_json(bench / "traffic" / f"{name}.json")


def reference(conf: dict):
    """The reference module the configuration names."""
    return importlib.import_module(f"bench.references.{conf['reference']}")


def reader(metric: str, bench: Path = BENCH):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bm: dict, cell_name: str, traced: bool) -> list:
    """The metric entries a run of ``cell_name`` reports: the
    end-to-end ones untraced, the per-layer ones traced; an entry with
    a ``workloads`` list only in those cells."""
    entries = bm["per_layer"] if traced else bm["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def model_config(conf: dict):
    """The program's model config for ``conf``: the repo's entry for
    ``conf['arch']`` with ``conf['program']``'s fields replaced, then
    checked key by key against the sizes the file states."""
    from dataclasses import replace
    from repro.configs import get_config
    cfg = replace(get_config(conf["arch"]), **conf.get("program", {}))
    ref = reference(conf)
    for field, key in ref.FIELDS.items():
        got, want = getattr(cfg, field), conf[key]
        if got != want:
            raise ValueError(f"config {conf['name']}: the program's "
                             f"{field} is {got}, the file states "
                             f"{key} = {want}")
    return cfg
