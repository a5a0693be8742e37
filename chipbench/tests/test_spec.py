"""Everything BENCHMARK.json names resolves to a file of its own."""

import importlib
import json
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench import traffic as T

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [e["name"] for group in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[group]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in SPEC["end_to_end"] + SPEC["per_layer"])
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_configurations_resolve(conf):
    assert conf["file"] == f"chipbench/configs/{conf['name']}.json"
    data = harness.load_config(conf["name"])
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    assert any(c["config"] == conf["name"] for c in SPEC["workloads"])
    limit = data["check"]["max_logit_gap"]
    assert 0 < limit < float("inf")
    for key in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff", "vocab_size"):
        assert key not in conf["reduced"]  # widths are never cut


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cells_resolve(cell):
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] == 1
    harness.load_mix(cell["traffic"])
    assert (T.SLOTS, T.MAX_SEQ, T.BACKLOG) == (1, 4096, 1)
    assert any(c["name"] == cell["config"] for c in SPEC["configs"])
    e2e = harness.metrics_for(SPEC, cell["name"], trace=False)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert harness.metrics_for(SPEC, cell["name"], trace=True)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics_resolve(metric):
    mod = importlib.import_module(f"chipbench.metrics.{metric['name']}")
    assert callable(mod.read)
    cells = {c["name"] for c in SPEC["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "moves" in metric:
        moved = next(m for m in SPEC["end_to_end"] if m["name"] == metric["moves"])
        for cell in metric.get("workloads", cells):
            assert cell in moved.get("workloads", cells)
    if metric in SPEC["per_layer"]:
        assert set(metric["workloads"]) == cells  # every cell reports every layer metric
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")


def test_without_the_program_beside_it_a_run_prints_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    cell = SPEC["workloads"][0]["name"]
    out = subprocess.run([sys.executable, *SPEC["command"][1:], "--workload", cell, "--seed",
                          str(2**33 + 5), "--seconds", "1", "--trace", "0"],
                         cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
