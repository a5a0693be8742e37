"""The idle split by engine span: on made-up events, on a small engine trace
recorded on the chip (``record_engine_trace.py``), and the four readers
that read it, which stay silent on a run with no trace."""

from pathlib import Path

import pytest

from chipbench import engine_spans as E
from chipbench import harness, xplane
from chipbench.metrics import admit_idle_ms, launch_idle_ms, prefill_mfu, sample_idle_ms

DATA = Path(__file__).resolve().parent / "data" / "engine.xplane.pb"
READERS = [admit_idle_ms, launch_idle_ms, prefill_mfu, sample_idle_ms]


def _issued(runs, offset=0):
    """The runtime's host events around each program run, ``offset`` ns
    after the device's clock, 1 ns of dispatch and of completion either side."""
    out = []
    for _, s, e in runs:
        out += [(E.RUNTIME[0], s + offset - 1, s + offset),
                (E.RUNTIME[1], e + offset, e + offset + 1)]
    return out


def _split_of(ops, host):
    return E.split_events({"/device:TPU:0": ops}, {"/device:TPU:0": ops}, host + _issued(ops))


def test_idle_goes_to_the_innermost_span_that_covers_it():
    # one admission (0..40) and one decode step (50..90) inside a tick (0..100);
    # the device runs 10..20 (prefill) and 60..70 (decode)
    host = [("tick", 0, 100),
            ("engine.admit", 0, 40), ("engine.launch", 2, 12), ("engine.wait", 12, 25),
            ("engine.sample", 25, 35),
            ("engine.decode", 50, 90), ("engine.launch", 52, 58), ("engine.wait", 58, 72),
            ("engine.sample", 72, 80)]
    ops = [("jit_engine_prefill/fusion", 10, 20), ("jit_engine_decode/fusion", 60, 70)]
    out = _split_of(ops, host)
    assert out["window_ns"] == 100 and out["total_idle_ns"] == 80
    assert out["offsets_ns"] == [(10, 0)]
    assert out["count"] == {"engine.admit": 1, "engine.decode": 1}
    assert out["idle_ns"] == {
        "engine.admit": 2 + 5, "engine.admit/engine.launch": 8,
        "engine.admit/engine.wait": 5, "engine.admit/engine.sample": 10,
        "engine.decode": 2 + 10, "engine.decode/engine.launch": 6,
        "engine.decode/engine.wait": 2 + 2, "engine.decode/engine.sample": 8,
        E.OUTSIDE: 10 + 10}
    assert sum(out["idle_ns"].values()) == out["total_idle_ns"]


def test_overlapping_spans_give_idle_to_the_one_that_started_last():
    pieces = E.innermost([("a", 0, 10), ("b", 5, 20), ("c", 6, 8)], ["a", "b", "c"])
    assert pieces == [(0, 5, "a"), (5, 6, "b"), (6, 8, "c"), (8, 20, "b")]
    assert E.attribute([(0, 30)], pieces) == {"a": 5, "b": 13, "c": 2, E.OUTSIDE: 10}


def test_a_program_without_engine_spans_puts_every_idle_ns_outside():
    host = [("generate", 0, 5), ("tick", 5, 100)]
    out = _split_of([("jit__lambda/fusion", 30, 60)], host)
    assert out["idle_ns"] == {E.OUTSIDE: 70}
    assert out["count"] == {"engine.admit": 0, "engine.decode": 0}


def test_device_clock_offset_from_the_runtime_events():
    # the device writes its runs 300 ns early: dispatch and completion pin it
    runs = [("jit_engine_decode(1)", 1000, 2000), ("jit__argmax(2)", 2500, 2510)]
    assert E.clock_offsets(runs, _issued(runs, offset=300)) == [(1000, 300)]
    # a trace that lost an event at its start pairs one place further on
    runs = runs + [("jit_engine_decode(1)", 3100, 4900), ("jit__argmax(2)", 5200, 5230)]
    assert E.clock_offsets(runs, _issued(runs, offset=300)[2:]) == [(1000, 300)]
    # events that no offset fits, or too few of them, give nothing
    host = _issued(runs[:2], offset=0) + _issued(runs[2:], offset=5000)
    assert E.clock_offsets(runs, host) is None
    assert E.clock_offsets(runs, _issued(runs[:1], offset=300)) is None


def test_each_chunk_of_runs_takes_its_own_offset():
    runs = [("jit_engine_decode(1)", 10_000 * i, 10_000 * i + 5000) for i in range(2 * E.CHUNK)]
    host = _issued(runs[:E.CHUNK], offset=300) + _issued(runs[E.CHUNK:], offset=700)
    offsets = E.clock_offsets(runs, host)
    assert offsets == [(0, 300), (10_000 * E.CHUNK, 700)]
    assert E.to_host([("op", 5, 6), ("op", 10_000 * E.CHUNK + 5, 10_000 * E.CHUNK + 6)],
                     offsets) == [("op", 305, 306),
                                  ("op", 10_000 * E.CHUNK + 705, 10_000 * E.CHUNK + 706)]
    # the split takes the device's events onto the host's clock
    host = [("tick", 0, 6000), ("engine.decode", 1100, 2600), ("engine.wait", 1100, 2400)]
    ops = [("jit_engine_decode/fusion", 1000, 2000)]
    out = E.split_events({"/device:TPU:0": ops}, {"/device:TPU:0": ops},
                         host + _issued(ops, offset=300))
    assert out["offsets_ns"] == [(1000, 300)]
    # the run moves to 1300..2300: idle in the wait before it (200) and after it (100)
    assert out["idle_ns"] == {E.OUTSIDE: 1100 + 3400, "engine.decode/engine.wait": 200 + 100,
                              "engine.decode": 200}


def test_nothing_to_read_gives_nothing():
    assert E.split_events({}, {}, [("tick", 0, 10)]) is None
    assert E.split_events({"/device:TPU:0": []}, {"/device:TPU:0": []}, []) is None
    # no runtime events to place the device's clock
    ops = [("jit_engine_decode/fusion", 2, 5)]
    assert E.split_events({"/device:TPU:0": ops}, {"/device:TPU:0": ops}, [("tick", 0, 10)]) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda m: m.__name__.split(".")[-1])
def test_readers_stay_silent_on_a_run_with_no_trace(reader):
    run = harness.Run("deepseek_7b_15l.doc_qa", 1, 10.0, 1.0, {}, None,
                      {"bf16_flops_per_s": 197e12}, [], [], 0, {})
    assert reader.read(run) is None


def _recorded():
    devices, modules, host = xplane.read_events(DATA, spans=xplane.HOST_SPANS + E.SPANS + E.RUNTIME)
    return devices, modules, host


def test_recorded_trace_split_sums_to_the_window_idle():
    devices, modules, host = _recorded()
    out = E.split_file(DATA)
    assert out == E.split_events(devices, modules, host)
    assert out["count"]["engine.admit"] >= 2 and out["count"]["engine.decode"] >= 5
    idle = out["idle_ns"]
    admit = sum(v for k, v in idle.items() if k.startswith("engine.admit"))
    kids = sum(idle.get(f"engine.decode/{c}", 0.0) for c in E.CHILDREN)
    parts = admit + kids + idle.get("engine.decode", 0.0) + idle.get(E.OUTSIDE, 0.0)
    assert set(idle) <= {"engine.decode", E.OUTSIDE} | {
        f"{p}/{c}" for p in E.PARENTS for c in E.CHILDREN} | {"engine.admit"}
    assert abs(parts - out["total_idle_ns"]) <= 1e3
    ops = E.to_host(next(iter(devices.values())), out["offsets_ns"])
    harness_spans = [(s, e) for n, s, e in host if n in xplane.HOST_SPANS]
    lo, hi = min(s for s, _ in harness_spans), max(e for _, e in harness_spans)
    assert out["total_idle_ns"] == pytest.approx(hi - lo - xplane.busy_ns(ops, lo, hi))


def test_recorded_trace_decode_runs_lie_between_launch_and_wait():
    """Clock alignment: with the device's events moved by the offsets that
    the runtime's events bound, each run of the decode program starts after
    its step's ``engine.launch`` starts and ends before its ``engine.wait``
    ends, for at least 99% of the steps."""
    _, modules, host = _recorded()
    offsets = E.clock_offsets(next(iter(modules.values())), host)
    assert offsets is not None and all(abs(d) < 3e6 for _, d in offsets)
    runs = E.to_host(next(iter(modules.values())), offsets)
    steps = sorted((s, e) for n, s, e in host if n == "engine.decode")
    child = {c: sorted((s, e) for n, s, e in host if n == c) for c in E.CHILDREN}

    def inside(name, s, e):
        return next(x for x in child[name] if s <= x[0] and x[1] <= e)

    decode = sorted((s, e) for n, s, e in runs if n.startswith("jit_engine_decode("))
    assert not any(n.startswith("jit__lambda") for n, _, _ in runs)
    assert len(decode) == len(steps)
    held = 0
    for (s, e), (rs, re_) in zip(steps, decode):
        launch, wait = inside("engine.launch", s, e), inside("engine.wait", s, e)
        held += launch[0] <= rs and re_ <= wait[1]
    assert held >= 0.99 * len(steps)
