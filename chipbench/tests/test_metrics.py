"""Metric readers on a run made by hand: what each reads, and that a share
of a peak stays silent when the run holds nothing for it."""

from types import SimpleNamespace as NS

import pytest

from chipbench import arith, harness
from chipbench import traffic as T
from chipbench.metrics import decode_hbm_roofline, host_ms_per_tick, itl_p95_ms, tokens_per_s

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MODEL = harness.load_config("deepseek_7b_15l")["model"]
MIX = T.Mix(name="m", prompt_lens=(8,), prompt_weights=(1,), output_mean=2, output_cap=4)


def _tick(start, end, phases, kv):
    events = [NS(phase=p, duration_s=0.01, tokens=1) for p in phases]
    return harness.Tick(start, end, events, kv)


def _run(ticks, traced, programs, peak=PEAK, recs=()):
    trace = {"modules": programs, "busy_s": 1.0, "window_s": 2.0} if programs else {}
    return harness.Run("c.m", 1, 10.0, 1.0, MODEL, MIX, peak, list(recs), ticks, traced, trace)


TICKS = [_tick(0.00, 0.03, ["prefill", "decode"], [2049]),
         _tick(0.03, 0.05, ["decode"], [2050]),
         _tick(0.05, 0.07, ["decode"], [2051]),
         _tick(0.07, 0.09, ["decode"], [2052])]


def test_decode_roofline_reads_the_program_that_ran_once_per_step():
    programs = {"jit__lambda(11)": [3, 0.045],  # decode, the three traced steps
                "jit_argmax(12)": [3, 0.0001],  # runs once per step, for microseconds
                "jit__lambda(13)": [1, 0.2]}  # the prefill
    least = sum(arith.decode_bytes(MODEL, n) / PEAK["hbm_bytes_per_s"] for n in (2049, 2050, 2051))
    got = decode_hbm_roofline.read(_run(TICKS, 3, programs))
    assert got == pytest.approx(100 * least / 0.045)


def test_decode_roofline_takes_a_run_lost_at_the_edge_of_the_trace():
    ticks = [_tick(0.02 * i, 0.02 * i + 0.015, ["decode"], [2048 + i]) for i in range(300)]
    programs = {"jit__lambda(11)": [298, 4.47], "jit_argmax(12)": [300, 0.01],
                "jit__lambda(13)": [3, 0.6]}
    least = sum(arith.decode_bytes(MODEL, 2048 + i) / PEAK["hbm_bytes_per_s"] for i in range(300))
    got = decode_hbm_roofline.read(_run(ticks, 300, programs))
    assert got == pytest.approx(100 * least / 300 * 298 / 4.47)


@pytest.mark.parametrize("programs, peak", [
    ({"jit__lambda(11)": [6, 0.05]}, PEAK),  # no program ran once per traced step
    ({}, PEAK),  # no trace
    ({"jit__lambda(11)": [3, 0.045]}, None),  # no peak for this device
])
def test_decode_roofline_stays_silent_without_its_inputs(programs, peak):
    assert decode_hbm_roofline.read(_run(TICKS, 3, programs, peak)) is None


def test_host_time_per_tick_leaves_out_logged_service():
    # ticks of 30, 20, 20, 20 ms, with 20, 10, 10, 10 ms of logged service
    assert host_ms_per_tick.read(_run(TICKS, 0, {})) == pytest.approx(10.0)


def test_tokens_and_gaps_count_only_what_the_window_saw():
    recs = [NS(times=[1.0, 1.0, 1.02, 1.05]), NS(times=[9.99, 10.01, 10.02])]
    run = _run(TICKS, 0, {}, recs=recs)
    assert tokens_per_s.read(run) == pytest.approx(5 / 10.0)
    # gaps 0, 20, 30 ms inside the window; the two that close after 10 s are left out
    assert itl_p95_ms.read(run) == pytest.approx(30 - 0.05 * 2 * 10)
