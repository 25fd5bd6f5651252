"""``BENCHMARK.json`` and the files it names.

A cell is found by name: its configuration is
``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json``, the limits of its correctness check
``bench/checks/<workload>.json``, and each metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a mix or
a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file, with its "name"
    mix: dict               # the traffic file
    check: dict             # the check file: limits and sample size
    end_to_end: List[dict]  # BENCHMARK.json entries that apply here
    per_layer: List[dict]


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if len(entries) != 1:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"unknown workload {name!r} (known: {known})")
    w = entries[0]
    conf_entry = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    config = _load(root / conf_entry["file"])
    config["name"] = w["config"]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, name) and m["moves"] in moved]
    return Cell(name=name, chips=w["chips"], config=config,
                mix=_load(BENCH / "traffic" / f"{w['traffic']}.json"),
                check=_load(BENCH / "checks" / f"{name}.json"),
                end_to_end=e2e, per_layer=layer)


def load_file(path: Path, name: str) -> ModuleType:
    """Import the Python file ``path`` as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return load_file(BENCH / "metrics" / f"{metric}.py",
                     "bench_metric_" + metric.replace(".", "_")).read


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip, by JAX's ``device_kind``."""
    table = _load(BENCH / "peaks.json")["chips"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]
