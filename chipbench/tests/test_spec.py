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
    assert broken_floors(data) == []


# The model-configs guide, section 4: no width is ever cut (hidden size, head
# sizes, feed-forward and expert widths, experts per token, window and state
# sizes); a sliced vocabulary keeps at least an eighth, and a layer with
# experts holds at least 8 of them here.
WIDTHS = {"hidden_size", "d_model", "intermediate_size", "d_ff", "moe_intermediate_size",
          "num_experts_per_tok", "sliding_window", "window_size", "d_state", "mamba_d_state",
          "d_conv", "mamba_d_conv", "expand", "mamba_expand"}
EXPERT_COUNTS = ("num_experts", "num_local_experts", "n_routed_experts")


def is_width(key: str) -> bool:
    return key in WIDTHS or key.endswith(("_dim", "_rank", "hidden_size",
                                          "intermediate_size", "_d_state", "_expand"))


def broken_floors(data: dict) -> list[str]:
    """What a configuration file's ``reduced`` cuts below the floors."""
    cut, published, as_run = data["reduced"], data["published"], data["as_run"]
    out = [f"{k}: a width" for k in cut if is_width(k)]
    out += [f"{k}: not stated as published and as run" for k in cut
            if k not in published or k not in as_run]
    if "vocab_size" in cut and "vocab_size" in as_run:
        if 8 * as_run["vocab_size"] < published["vocab_size"]:
            out.append("vocab_size: under an eighth of the published vocabulary")
    out += [f"{k}: fewer than 8 experts held" for k in EXPERT_COUNTS
            if k in cut and as_run.get(k, 0) < 8]
    return out


@pytest.mark.parametrize("name", harness.config_names())
def test_every_configuration_file_keeps_the_floors(name):
    data = harness.load_config(name)
    assert broken_floors(data) == []
    assert data["model"]["family"] == data["small"]["family"]
    harness.load_mix(data["rehearsal_traffic"])


@pytest.mark.parametrize("cut, as_run, broken", [
    (["vocab_size"], {"vocab_size": 16384}, []),  # a quarter of 65536
    (["vocab_size"], {"vocab_size": 4096}, ["vocab_size: under an eighth of the published "
                                            "vocabulary"]),
    (["num_experts"], {"num_experts": 8}, []),
    (["num_experts"], {"num_experts": 4}, ["num_experts: fewer than 8 experts held"]),
    (["hidden_size"], {"hidden_size": 2048}, ["hidden_size: a width"]),
    (["moe_intermediate_size"], {"moe_intermediate_size": 7168},
     ["moe_intermediate_size: a width"]),
    (["head_dim"], {"head_dim": 64}, ["head_dim: a width"]),
    (["mamba_d_state"], {"mamba_d_state": 8}, ["mamba_d_state: a width"]),
    (["num_hidden_layers"], {}, ["num_hidden_layers: not stated as published and as run"]),
])
def test_floors_refuse_a_cut_width_and_too_small_a_share(cut, as_run, broken):
    published = {"vocab_size": 65536, "num_experts": 16, "hidden_size": 4096,
                 "moe_intermediate_size": 14336, "head_dim": 128, "mamba_d_state": 16,
                 "num_hidden_layers": 32}
    assert broken_floors({"reduced": cut, "published": published, "as_run": as_run}) == broken


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
