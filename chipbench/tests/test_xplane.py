"""The trace reducer: busy union, idle share and top ops."""

from pathlib import Path

import pytest

from chipbench import xplane

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps_and_nesting():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (6, 6), (8, 9), (8, 8.5)]) == [
        (0, 3), (5, 7), (8, 9)]


def test_busy_idle_and_top_ops_on_synthetic_events():
    # a loop (20..40) holds two ops of its body; the rest stand alone
    ops = [("fusion.1", 0, 10), ("loop", 20, 40), ("fusion.1", 20, 28), ("dot", 30, 36),
           ("copy", 90, 120)]
    host = [("tick", 0, 45), ("wait_arrival", 45, 90), ("tick", 90, 125)]
    assert xplane.busy_ns(ops, 0, 100) == 10 + 20 + 10
    out = xplane.reduce({"/device:TPU:0": ops}, host, 0, 100)
    assert out["busy_s"] == pytest.approx(40e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    # self time: fusion.1 10 + 8, the loop 20 - 8 - 6, dot 6, copy 10 inside the window
    assert out["device_ops"] == [["fusion.1", pytest.approx(18e-9)],
                                 ["copy", pytest.approx(10e-9)],
                                 ["dot", pytest.approx(6e-9)],
                                 ["loop", pytest.approx(6e-9)]]
    assert sum(t for _, t in out["device_ops"]) == pytest.approx(out["busy_s"])
    # longest gap 40..90 lies mostly in wait_arrival; 10..20 inside a tick
    assert out["idle_gaps"][0] == ["wait_arrival", pytest.approx(50e-9)]
    assert out["idle_gaps"][1] == ["tick", pytest.approx(10e-9)]


def test_ops_are_named_after_their_program():
    op = "%fusion.95 = bf16[12288]{0:T(1024)(128)(2,1)S(1)} fusion(bf16[30,3072,12288]{2,1,0}"
    assert xplane.short_name(op, "jit__lambda(13579960387038585310)") == \
        "jit__lambda/%fusion.95 bf16[12288]"
    assert xplane.short_name("%while.12 = (s32[]{:T(128)}, bf16[1])", "?") == "?/%while.12 (s32[]"
    assert xplane.op_key(op) == "%fusion.95 bf16[12288]"


def test_busy_is_averaged_over_device_planes():
    out = xplane.reduce({"a": [("x", 0, 10)], "b": [("x", 0, 30)]}, [], 0, 100)
    assert out["busy_s"] == pytest.approx(20e-9)


def test_nothing_to_read_gives_nothing():
    assert xplane.reduce({}, [], 0, 10) == {}
    assert xplane.reduce({"/device:TPU:0": []}, [], 0, 10) == {}


def test_program_runs_are_counted_and_timed():
    runs = [("jit_a(1)", 0, 10), ("jit_b(2)", 10, 11), ("jit_a(1)", 20, 40)]
    assert xplane.module_totals(runs) == {"jit_a(1)": [2, pytest.approx(30e-9)],
                                          "jit_b(2)": [1, pytest.approx(1e-9)]}


def test_trace_recorded_on_the_chip():
    devices, modules, host = xplane.read_events(DATA, spans=("tick", "wait_arrival"))
    assert list(devices) == ["/device:TPU:0"]
    ops = devices["/device:TPU:0"]
    ticks = [(s, e) for n, s, e in host if n == "tick"]
    waits = [(s, e) for n, s, e in host if n == "wait_arrival"]
    assert len(ticks) == 4 and len(waits) == 4
    lo, hi = ticks[0][0], waits[-1][1]
    out = xplane.reduce(devices, host, lo, hi)
    # the device works inside the ticks and idles through the 20 ms sleeps
    assert 0 < out["busy_s"] < sum(e - s for s, e in ticks) * 1e-9
    idle = 1 - out["busy_s"] / out["window_s"]
    assert idle > sum(e - s for s, e in waits) / (hi - lo) * 0.9
    assert out["idle_gaps"][0][0] == "wait_arrival"
    assert out["idle_gaps"][0][1] >= 0.015
    assert sum(t for _, t in out["device_ops"]) <= out["busy_s"] * (1 + 1e-9)
    assert all(n.startswith("jit__lambda/%") for n, _ in out["device_ops"])
    assert all(s >= lo - 1e6 and e <= hi + 1e6 for _, s, e in ops if lo <= s <= hi)
    # one program, run three times in each of the four ticks
    (name, (runs, secs)), = xplane.module_totals(modules["/device:TPU:0"]).items()
    assert name.startswith("jit__lambda(") and runs == 12
    assert 12 * 150e-6 < secs < 12 * 250e-6  # each run takes about 0.19 ms


def test_op_seconds_are_kept_per_program_run_name():
    # two runs of jit_a (a loop holding one op) and one of jit_b; one op ran outside
    ops = [("jit_a/%while.1 (s32[]", 0, 40), ("jit_a/%fusion.2 bf16[8]", 10, 30),
           ("jit_b/%fusion.2 f32[4]", 60, 70), ("jit_a/%while.1 (s32[]", 100, 120),
           ("jit_a/%fusion.2 bf16[8]", 105, 110), ("?/%copy.1 f32[2]", 200, 201)]
    runs = [("jit_a(11)", 0, 40), ("jit_b(12)", 55, 75), ("jit_a(11)", 100, 125)]
    out = xplane.reduce({"/device:TPU:0": ops}, [], 50, 150, programs={"/device:TPU:0": runs})
    assert out["op_seconds"] == {
        "jit_a(11)": {"%while.1 (s32[]": pytest.approx(35e-9),
                      "%fusion.2 bf16[8]": pytest.approx(25e-9)},
        "jit_b(12)": {"%fusion.2 f32[4]": pytest.approx(10e-9)},
        "?": {"%copy.1 f32[2]": pytest.approx(1e-9)}}
    assert out["modules"] == {"jit_a(11)": [2, pytest.approx(65e-9)],
                              "jit_b(12)": [1, pytest.approx(20e-9)]}
    # the window's breakdown is the same with or without the programs
    plain = xplane.reduce({"/device:TPU:0": ops}, [], 50, 150)
    assert {k: out[k] for k in plain} == plain and "op_seconds" not in plain
