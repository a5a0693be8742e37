"""The engine's phase spans in a CPU profile of a tiny served run: the five
names, their nesting, one span per logged service event, the programs'
stable names, no program dispatched while sampling, wall-clock stamps on
the profiler's clock, and tokens that do not depend on whether a profiler
session is on."""

from __future__ import annotations

import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_config
from repro.models import lm
from repro.obs import Tracer
from repro.serving.engine import Engine, Request, ServeConfig

PARENTS = ("engine.admit", "engine.decode")
CHILDREN = ("engine.launch", "engine.wait", "engine.sample")
# a wall stamp against the ends of a span on the profiler's clock: the two
# clocks are one (the offset reads 1-3 us on the CPU)
TOL_NS = 20_000


def _newest_xplane(d):
    files = sorted(d.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return files[-1]


def _host_events(path, names):
    """``(name, start_ns, end_ns)`` of host events whose name is in ``names``
    (or starts with ``PjitFunction(``), on the realtime clock: the profile's
    start time plus each event's offset."""
    pd = ProfileData.from_file(str(path))
    env = next(p for p in pd.planes if p.name == "Task Environment")
    base = dict(env.stats)["profile_start_time"]
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, base + e.start_ns, base + e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name in names or e.name.startswith("PjitFunction(")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _engine():
    cfg = get_config("starcoder2_3b").reduced(seq_chunk=8)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(slots=1, max_seq=64))
    eng.warmup([8, 12])
    return eng


def _requests():
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, 256, size=L).astype(np.int32),
                    max_new_tokens=n)
            for i, (L, n) in enumerate([(8, 4), (12, 1), (8, 3)])]


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    d = tmp_path_factory.mktemp("engine_profile")
    eng = _engine()
    eng.tracer, eng._trace = Tracer(), True
    reqs = _requests()
    jax.profiler.start_trace(str(d))
    for r in reqs:
        eng.submit(r)
    eng.drain()
    probes = []
    for _ in range(20):
        t = time.time_ns()
        with TraceAnnotation("clock.probe"):
            pass
        probes.append(t)
    jax.profiler.stop_trace()
    events = _host_events(_newest_xplane(d), PARENTS + CHILDREN + ("clock.probe",))
    return eng, reqs, events, probes


def _named(events, name):
    return [(s, e) for n, s, e in events if n == name]


def _parent_of(events, s, e):
    """The admit or decode span that holds ``[s, e)``."""
    held = [(n, ps, pe) for n, ps, pe in events if n in PARENTS and ps <= s and e <= pe]
    assert len(held) == 1, (s, e, held)
    return held[0]


def test_five_names_each_child_inside_its_parent(profiled):
    _, _, events, _ = profiled
    assert {n for n, _, _ in events} >= set(PARENTS + CHILDREN)
    kids = {}
    for n, s, e in events:
        if n in CHILDREN:
            kids.setdefault(_parent_of(events, s, e), []).append((s, n))
    parents = [(n, s, e) for n, s, e in events if n in PARENTS]
    assert sorted(kids) == sorted(parents)
    # in each parent, once each and in order: launch, wait, sample
    for got in kids.values():
        assert [n for _, n in sorted(got)] == list(CHILDREN)
    # parents do not overlap one another
    spans = sorted((s, e) for _, s, e in parents)
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))


def test_one_span_per_service_event(profiled):
    eng, _, events, _ = profiled
    phases = [ev.phase for ev in eng.service_log]
    assert len(_named(events, "engine.admit")) == phases.count("prefill") == 3
    assert len(_named(events, "engine.decode")) == phases.count("decode") == 5


def test_programs_have_stable_names(profiled):
    eng, _, events, _ = profiled
    called = {n for n, _, _ in events if n.startswith("PjitFunction(")}
    assert {"PjitFunction(engine_prefill)", "PjitFunction(engine_decode)"} <= called
    assert not any("lambda" in n for n in called)
    text = eng._prefill.lower(eng.params, jnp.zeros((1, 8), jnp.int32)).as_text()
    assert "@jit_engine_prefill" in text.splitlines()[0]


def test_programs_pick_the_tokens(profiled):
    """No jitted call starts inside any ``engine.sample`` span: the prefill
    and decode programs return the greedy ids, and sampling only copies
    them to the host, on every admission and every decode step."""
    _, _, events, _ = profiled
    samples = _named(events, "engine.sample")
    assert len(samples) == 3 + 5
    calls = [s for n, s, _ in events if n.startswith("PjitFunction(")]
    assert calls
    assert [c for c in calls if any(s <= c < e for s, e in samples)] == []


def _phases(events, parent):
    """Per ``parent`` span: its start and end and those of its children."""
    out = []
    for s, e in _named(events, parent):
        kids = {n: (ks, ke) for n, ks, ke in events if n in CHILDREN and s <= ks and ke <= e}
        out.append(dict(kids, span=(s, e)))
    return out


def test_wall_stamps_are_taken_when_each_event_happens(profiled):
    """Service starts fall between the parent span's start and the launch;
    a token's time falls after its sample, on the profiler's clock."""
    eng, reqs, events, probes = profiled
    admits, decodes = _phases(events, "engine.admit"), _phases(events, "engine.decode")

    def between(t_s, lo, hi):
        return lo - TOL_NS <= t_s * 1e9 <= hi + TOL_NS

    prefills = [ev for ev in eng.service_log if ev.phase == "prefill"]
    steps = [ev for ev in eng.service_log if ev.phase == "decode"]
    for ev, r, ph in zip(prefills, reqs, admits):
        assert between(ev.t, ph["span"][0], ph["engine.launch"][0])
        assert r.t_admit == ev.t
        assert between(r.t_first_token, ph["engine.sample"][1], ph["span"][1])
    for ev, ph in zip(steps, decodes):
        assert between(ev.t, ph["span"][0], ph["engine.launch"][0])
    assert reqs[1].t_done == reqs[1].t_first_token  # one token: done at its prefill
    last = decodes[2]  # request 0 takes the first three decode steps
    assert between(reqs[0].t_done, last["engine.sample"][1], last["span"][1])
    # the repro.obs spans carry the same stamps
    obs = {s.name: s for s in eng.tracer.spans if s.track == "req[2]"}
    assert obs["prefill"].t == reqs[2].t_admit
    assert obs["queue"].dur == pytest.approx(reqs[2].t_admit - reqs[2].arrival_s)
    # time.time_ns() just before an annotation against the annotation's start
    starts = [s for s, _ in _named(events, "clock.probe")]
    offsets = [s - t for s, t in zip(starts, probes)]
    assert len(offsets) == len(probes)
    assert 0 <= statistics.median(offsets) < TOL_NS


def test_tokens_bit_equal_without_a_profiler_session(profiled):
    _, reqs, _, _ = profiled
    eng = _engine()
    again = _requests()
    for r in again:
        eng.submit(r)
    eng.drain()
    assert [r.tokens_out for r in again] == [r.tokens_out for r in reqs]
    assert all(len(r.tokens_out) == r.max_new_tokens for r in again)
