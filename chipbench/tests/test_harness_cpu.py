"""A whole run on the CPU at small widths, through ``harness.run_cell``: the
look for a chip is skipped, everything else is a benchmark run. A sound run
is correct; each fault that a one-slot serving cell can have, planted in
the timed path, makes ``correct`` come out false; and the fp8 control,
judged through the same checks against a limit set for these widths, is not
correct.
"""

import json
import time

import jax.numpy as jnp
import pytest

from chipbench import calibrate, harness
from chipbench import traffic as T
from repro.models import lm
from repro.serving.engine import Engine

SPEC = harness.load_spec()
# each configuration file's small model block and the traffic it is
# rehearsed on, so that every configuration's code path (MHA with SwiGLU,
# GQA with GELU, ...) stays under test, listed in BENCHMARK.json or not
CONFIGS = {name: harness.load_config(name) for name in harness.config_names()}
SMALL = {name: conf["small"] for name, conf in CONFIGS.items()}
SEED = 2**33 + 1
SMALL_MIX = T.Mix(name="small", prompt_lens=(8, 24), prompt_weights=(1, 1), output_mean=6,
                  output_cap=20)
CELLS = [f"{name}.{conf['rehearsal_traffic']}" for name, conf in CONFIGS.items()]
# The configurations' limits hold at their own widths. At these small widths
# the program's widest gap read 0 to 0.025 and the fp8 control's 0.09 to
# 0.18 (both configurations, 1 s and 3 s windows, seeds 7, 2**31 + 5 and
# SEED), so the control is judged here against a limit between the two.
SMALL_LIMIT = 0.06


def run(cell_name: str, *, trace=False, seconds=1.0, tmp_path=None):
    cell = harness.cell_named(cell_name)
    return harness.run_cell(SPEC, cell, SEED, seconds, trace, time.perf_counter(), peak=None,
                            model=SMALL[cell["config"]], mix=SMALL_MIX, out_dir=tmp_path)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path):
    res = run(cell, tmp_path=tmp_path)
    json.dumps(res)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 3
    assert list(res)[-1] == "check"
    names = {m["name"] for m in harness.metrics_for(SPEC, cell, trace=False)}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["check"]["checked_tokens"]["value"] >= 20


def test_traced_run_reports_host_metrics(tmp_path):
    res = run("deepseek_7b_15l.doc_qa", trace=True, tmp_path=tmp_path)
    assert res["correct"]
    # no peak table on the CPU: the shares of a peak stay silent, never 0
    assert set(res["metrics"]) == {"host_ms_per_tick"}


def _decode_keeps_state(p, cfg, tok, pos, caches):
    logits, _ = _DECODE(p, cfg, tok, pos, caches)
    return logits, caches


def _decode_alters_token(p, cfg, tok, pos, caches):
    logits, new = _DECODE(p, cfg, tok, pos, caches)
    return jnp.roll(logits, 1, axis=-1), new


_DECODE = lm.decode_step

FAULTS = {
    "decode_returns_state_unchanged": (lm, "decode_step", _decode_keeps_state),
    "slot_write_dropped": (Engine, "_write_slot", staticmethod(lambda full, one, slot, L: full)),
    "token_altered_where_produced": (lm, "decode_step", _decode_alters_token),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_timed_path_is_not_correct(fault, cell, monkeypatch, tmp_path):
    owner, attr, broken = FAULTS[fault]
    monkeypatch.setattr(owner, attr, broken)
    res = run(cell, tmp_path=tmp_path)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_is_not_correct(cell):
    c = harness.cell_named(cell)
    row = calibrate.reading(c, SEED, 1.0, True, model=SMALL[c["config"]], mix=SMALL_MIX,
                            limit=SMALL_LIMIT)
    assert row["program_correct"], row
    assert not row["control_correct"], row
    assert row["checked_requests"] >= harness.CHECK_MIN_REQUESTS
