"""BENCHMARK.json against the benchmark's contract, and the files it
names: every cell finds its configuration, mix, check limits and metric
readers by name.  Also: the command refuses to run without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer",
                      "moves"}}


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"][:2] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("group", sorted(KEYS))
def test_entries(group):
    names = [e["name"] for e in B[group]]
    assert len(names) == len(set(names))
    for e in B[group]:
        extra = {"workloads"} if group in ("end_to_end", "per_layer") \
            else set()
        assert KEYS[group] <= set(e) <= KEYS[group] | extra, e
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e and isinstance(e[k], str):
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_cells_and_configs():
    configs = {c["name"]: c for c in B["configs"]}
    used = {w["config"] for w in B["workloads"]}
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(
        1, len(B["workloads"]) // 2)
    for c in B["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).exists()
        assert c["source"].startswith("https://")
        conf = json.loads((ROOT / c["file"]).read_text())
        for k in c["reduced"]:
            assert NAME.match(k) and k in conf["reduced_from"]
            assert not k.endswith(("_dim", "_rank", "_size"))
    for w in B["workloads"]:
        assert w["chips"] in (1, 4) and w["name"] == \
            f"{w['config']}.{w['traffic']}"


def test_metrics_and_bounds():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in B["workloads"]}
    layers = {}
    for m in B["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        for w in m["workloads"]:
            assert "workloads" not in e2e[m["moves"]] or \
                w in e2e[m["moves"]]["workloads"]
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("name", [w["name"] for w in B["workloads"]])
def test_every_cell_resolves(name):
    c = spec.cell(name)
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(m["name"]))
    assert set(c.check["limits"]) == {"widest_gap", "mean_gap"}
    assert c.mix["warm"]["min_s"] <= c.mix["warm"]["max_s"]


def test_every_metric_has_a_reader_and_peaks_exist():
    for m in B["end_to_end"] + B["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        spec.peaks("no such chip")


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", B["workloads"][0]["name"],
                        "--seed", str(2**40 + 1), "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
