"""The benchmark's harness: one cell, one seed, one measured window.

Everything a cell needs is found by name: the configuration in
``chipbench/configs/<config>.json``, its model family (everything that
depends on the layer kinds: the program's config, the weight layout, the
reference's layers, the arithmetic, the tick counters) in
``chipbench/families/<family>.py``, the traffic mix in
``chipbench/traffic/<traffic>.json``, and each metric's reader in
``chipbench/metrics/<metric>.py``, whose ``read(run)`` returns a number or
``None`` when the run holds nothing for it to read.

A run builds the weights from the seed, warms up every prompt length of the
mix through the engine's own ``submit``/``tick`` (so the slot write and the
host's eager ops compile too), then drives ``repro.serving.engine.Engine``
in wall time for ``seconds`` as a closed loop that keeps ``BACKLOG``
requests waiting. Token times are stamped on the benchmark's clock when
``tick`` returns. After the window every request that was started is served
to its end, ``peak_bytes_in_use`` is read, the engine is freed, and a seeded
sample of the finished requests goes through the plain reference
(``reference.py``) to decide ``correct``.
"""

from __future__ import annotations

import argparse
import collections
import gc
import importlib
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from chipbench import traffic as T

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"  # fixed: the path is part of the cache key
OUT = ROOT / "results" / "chipbench"
DRAIN_S = 60.0  # how long past the window's close started requests may take
TRACE_S = 10.0  # the traced part of a ``--trace 1`` window
CHECK_TOKENS = 512  # served tokens the reference checks, at least
CHECK_MIN_REQUESTS = 4  # ... from at least this many requests
CHECK_REQUESTS = 16  # ... and at most this many


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------


@dataclass
class Rec:
    """One request, and when each of its tokens became visible, on the
    window's clock."""

    draw: T.Draw
    req: object  # repro.serving.engine.Request
    times: list = field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return len(self.draw.prompt)

    @property
    def done(self) -> bool:
        return self.req.t_done is not None


@dataclass
class Tick:
    start: float
    end: float
    events: list  # the engine's ServiceEvents of this tick
    decode_kv: list  # per decoded token, the valid cache positions it read
    counters: dict = field(default_factory=dict)  # the family's tick_counters after it


@dataclass
class Run:
    """What metric readers read."""

    cell: str
    seed: int
    seconds: float
    setup_s: float
    model: dict  # the configuration's sizes
    mix: T.Mix
    peak: dict | None
    recs: list
    ticks: list  # ticks that started inside the window
    traced_ticks: int  # how many of them, from the first, the trace holds
    trace: dict  # xplane.reduce of the trace, with "scopes"; empty without --trace 1


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(spec: dict, name: str) -> dict:
    for c in spec["workloads"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"error: no workload {name!r} in BENCHMARK.json")


def cell_named(name: str) -> dict:
    """A one-chip cell from ``<config>.<traffic>``, whether or not
    BENCHMARK.json lists it (for the calibration script and the tests)."""
    config, traffic = name.split(".")
    return {"name": name, "config": config, "traffic": traffic, "chips": 1}


def load_config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def config_names() -> list[str]:
    """Every configuration file's name, listed in BENCHMARK.json or not."""
    return sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


def load_mix(name: str) -> T.Mix:
    return T.load_mix(BENCH / "traffic" / f"{name}.json")


def metrics_for(spec: dict, cell: str, trace: bool) -> list[dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    return importlib.import_module(f"chipbench.metrics.{name}").read


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------


def build_engine(m: dict, seed: int):
    import jax

    from chipbench import families, weights
    from repro.models import lm
    from repro.serving.engine import Engine, ServeConfig

    cfg = families.of(m).program_config(m)
    params = weights.served_params(m, seed)
    shape = lambda t: jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), t)
    want = lm.abstract_model(cfg)
    if jax.tree.structure(params) != jax.tree.structure(want) or shape(params) != shape(want):
        raise ValueError("the seeded weights do not match the program's parameter tree")
    return Engine(cfg, params, ServeConfig(slots=T.SLOTS, max_seq=T.MAX_SEQ))


def warm_up(engine, mix: T.Mix) -> None:
    """One request per prompt length through submit/tick: prefill, slot
    write, decode and the host's eager ops all compile here."""
    from repro.serving.engine import Request

    for i, L in enumerate(sorted(set(mix.prompt_lens))):
        engine.submit(Request(rid=-1 - i, prompt=np.zeros(L, np.int32), max_new_tokens=2))
    engine.drain()
    engine.completed.clear()
    engine.service_log.clear()


class CompileClock:
    """Counts JAX backend compiles and persistent-cache reads (copied from
    the program's chip smoke run)."""

    def __init__(self):
        import jax

        self.totals = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.totals["compiles"] += 1
            self.totals["compile_s"] += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.totals["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.totals)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class Load:
    """Keeps ``T.BACKLOG`` requests of the mix waiting for the engine."""

    def __init__(self, mix: T.Mix, seed: int, vocab: int):
        self.stream = T.requests(mix, seed, vocab)

    def feed(self, engine, recs: list, live: list) -> None:
        from repro.serving.engine import Request

        while len(engine.queue) < T.BACKLOG:
            d = next(self.stream)
            req = Request(rid=d.index, prompt=d.prompt, max_new_tokens=d.max_new_tokens)
            engine.submit(req)
            rec = Rec(d, req)
            recs.append(rec)
            live.append(rec)


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _tick(engine, clock, live: list, ticks: list, counters=None) -> None:
    n0 = len(engine.service_log)
    start = clock()
    with _annotate("tick"):
        engine.tick()
    end = clock()
    events = engine.service_log[n0:]
    admitted = {ev.rid for ev in events if ev.phase == "prefill"}
    kv = []
    for rec in list(live):
        n = len(rec.req.tokens_out)
        new = n - len(rec.times)
        if new > 0:
            rec.times += [end] * new
            if new - (rec.req.rid in admitted) > 0:
                kv.append(rec.prompt_len + n - 1)
        if rec.done:
            live.remove(rec)
    ticks.append(Tick(start, end, events, kv, counters(engine) if counters else {}))


def drive(engine, load: Load, seconds: float, *, trace_dir: Path | None = None,
          counters=None):
    """The measured window, then the drain. ``counters(engine)`` is read
    after each tick (the family's ``tick_counters``). Returns (recs, the
    ticks that started inside the window, how many of them the trace holds,
    drain seconds)."""
    import jax

    recs: list[Rec] = []
    live: list[Rec] = []
    ticks: list[Tick] = []
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(str(trace_dir))
    traced = 0
    while (now := clock()) < seconds:
        if tracing and now >= TRACE_S:
            jax.profiler.stop_trace()
            tracing, traced = False, len(ticks)
        with _annotate("generate"):
            load.feed(engine, recs, live)
        _tick(engine, clock, live, ticks, counters)
    if tracing:
        jax.profiler.stop_trace()
        traced = len(ticks)
    window_ticks = list(ticks)
    # the window has closed: nothing new is handed over; requests that never
    # started are withdrawn, every started one is served to its end
    engine.queue.clear()
    recs[:] = [r for r in recs if r.req.t_admit is not None]
    live[:] = [r for r in live if r.req.t_admit is not None]
    t_close = clock()
    while live and clock() - t_close < DRAIN_S:
        _tick(engine, clock, live, ticks, counters)
    return recs, window_ticks, traced, clock() - t_close


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------


def check_sample(recs: list, seed: int) -> list:
    """Finished requests drawn from the seed, the longest first, until both
    ``CHECK_TOKENS`` served tokens and ``CHECK_MIN_REQUESTS`` requests are
    in, or ``CHECK_REQUESTS`` requests."""
    done = [r for r in recs if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.tokens_out), -r.req.rid))
    rest = [done[i] for i in np.random.default_rng([int(seed), 3]).permutation(len(done))]
    chosen, tokens = [longest], len(longest.req.tokens_out)
    for r in rest:
        enough = tokens >= CHECK_TOKENS and len(chosen) >= CHECK_MIN_REQUESTS
        if enough or len(chosen) >= CHECK_REQUESTS:
            break
        if r is not longest:
            chosen.append(r)
            tokens += len(r.req.tokens_out)
    return chosen


def expected_tokens(rec: Rec) -> int:
    """Tokens the engine owes a request: all it asked for, unless the cache
    fills first (the engine stops at position ``MAX_SEQ - 1``)."""
    return min(rec.draw.max_new_tokens, T.MAX_SEQ - rec.prompt_len)


def judge(recs: list, sample: list, m: dict, seed: int, limit: float, *, control: bool = False):
    """(failed, checks, control checks): a request fails when it never
    finished or when it did not get every token it was owed; the sample's
    widest logit gap against the reference has to stay under the
    configuration's limit.

    With ``control`` the third item holds the same checks with the fp8
    control's gap, read at the same positions (the gap of the token that
    the control ranks first), for which ``correct`` has to come out false;
    without it, ``None``."""
    from chipbench import reference

    failed = sum(1 for r in recs if not r.done or len(r.req.tokens_out) != expected_tokens(r))
    n_tokens = sum(len(r.req.tokens_out) for r in sample)

    def checks(gaps):
        gap = float(max(g.max() for g in gaps)) if sample else float("inf")
        return {"max_logit_gap": {"value": gap, "limit": limit},
                "checked_tokens": {"value": n_tokens, "limit": 1},
                "failed_requests": {"value": failed, "limit": 0}}

    if not sample:
        return failed, checks([]), (checks([]) if control else None)
    gaps = reference.served_gaps(m, seed, [r.draw.prompt for r in sample],
                                 [list(r.req.tokens_out) for r in sample], fp8_control=control)
    if control:
        return failed, checks(gaps[0]), checks(gaps[1])
    return failed, checks(gaps), None


def is_correct(checks: dict) -> bool:
    return (checks["max_logit_gap"]["value"] <= checks["max_logit_gap"]["limit"]
            and checks["checked_tokens"]["value"] >= checks["checked_tokens"]["limit"]
            and checks["failed_requests"]["value"] <= checks["failed_requests"]["limit"])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(spec: dict, cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
             *, peak: dict | None, model: dict | None = None, mix: T.Mix | None = None,
             out_dir: Path = OUT) -> dict:
    """One run of ``cell``; returns the result line's object. ``model`` and
    ``mix`` replace the cell's files (small sizes for tests on the CPU)."""
    import jax

    from chipbench import families, scopes

    conf = load_config(cell["config"])
    m = dict(model or conf["model"], name=cell["config"])
    family = families.of(m)
    mix = mix or load_mix(cell["traffic"])
    limit = conf["check"]["max_logit_gap"]
    clock = CompileClock()
    stamps = {"process_start_to_devices": time.perf_counter() - t_start}

    t = time.perf_counter()
    engine = build_engine(m, seed)
    jax.block_until_ready(engine.params)
    stamps["weights_and_engine"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_up(engine, mix)
    stamps["warmup"] = time.perf_counter() - t
    load = Load(mix, seed, m["vocab_size"])
    trace_dir = None
    if trace:
        trace_dir = out_dir / "trace" / cell["name"]
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    before = clock.snapshot()
    setup_s = time.perf_counter() - t_start
    recs, ticks, traced, drain_s = drive(engine, load, seconds, trace_dir=trace_dir,
                                         counters=family.tick_counters)
    compiles = {k: v - before.get(k, 0) for k, v in clock.snapshot().items()}

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use", 0)}
    reduced: dict = {}
    t_read = time.perf_counter()
    if trace:
        reduced = _reduce_trace(trace_dir)
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    trace_read_s = time.perf_counter() - t_read
    t_scopes, before_scopes = time.perf_counter(), clock.snapshot()
    if reduced:
        reduced["scopes"] = scopes.program_scopes(engine, mix)
    scope_compiles = {k: v - before_scopes.get(k, 0) for k, v in clock.snapshot().items()}
    scopes_s = time.perf_counter() - t_scopes

    run = Run(cell["name"], seed, seconds, setup_s, m, mix, peak, recs, ticks, traced, reduced)
    metrics = {}
    for spec_m in metrics_for(spec, cell["name"], trace):
        value = reader(spec_m["name"])(run)
        if value is not None:
            metrics[spec_m["name"]] = {"value": value, "unit": spec_m["unit"]}

    sample = check_sample(recs, seed)
    del engine, load
    gc.collect()
    t_ref = time.perf_counter()
    failed, checks, _ = judge(recs, sample, m, seed, limit)
    ref_s = time.perf_counter() - t_ref

    diag = _diagnostics(run, compiles, drain_s, ref_s, device, trace_dir, sample)
    diag["setup_parts_s"] = stamps
    diag["setup_compiles"] = before
    diag["trace_read_s"] = trace_read_s
    diag["scope_map_s"] = scopes_s
    diag["scope_map_compiles"] = scope_compiles
    diag["program_time"] = scopes.coverage(reduced)
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell['name']}.seed{seed}.trace{int(trace)}.json").write_text(
        json.dumps(diag, indent=1))
    for k, v in diag.items():
        log(f"{k}: {v}")
    result = {"correct": is_correct(checks), "attempted": len(recs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["check"] = checks
    return result


def _reduce_trace(trace_dir: Path) -> dict:
    from chipbench import xplane

    path = xplane.newest_xplane(trace_dir)
    if path is None:
        return {}
    devices, modules, host = xplane.read_events(path)
    if not host:
        return {}
    return xplane.reduce(devices, host, min(s for _, s, _ in host), max(e for _, _, e in host),
                         programs=modules)


def _top(pairs, k: int = 5) -> list:
    """The ``k`` longest ``(seconds, at)`` as ``[ms, at]``."""
    return [[round(d * 1e3, 2), round(t, 2)] for d, t in sorted(pairs, reverse=True)[:k]]


def _diagnostics(run: Run, compiles: dict, drain_s: float, ref_s: float, device: dict,
                 trace_dir, sample) -> dict:
    return {
        "cell": run.cell, "seed": run.seed, "seconds": run.seconds,
        "setup_s": run.setup_s,
        "compiles_in_window": compiles,
        "requests": {"started": len(run.recs),
                     "completed_in_window": sum(1 for r in run.recs
                                                if r.done and r.times[-1] <= run.seconds),
                     "in_flight_at_close": sum(1 for r in run.recs
                                               if not (r.done and r.times[-1] <= run.seconds)),
                     "completed": sum(1 for r in run.recs if r.done)},
        "ticks_in_window": len(run.ticks),
        "service_s": {ph: sum(ev.duration_s for t in run.ticks for ev in t.events
                              if ev.phase == ph) for ph in ("prefill", "decode")},
        "slowest_ticks_ms_at_s": _top([(t.end - t.start, t.start) for t in run.ticks]),
        "slowest_prefills_ms_at_s": _top([(ev.duration_s, t.start) for t in run.ticks
                                          for ev in t.events if ev.phase == "prefill"]),
        "longest_gaps_between_ticks_ms_at_s": _top([(b.start - a.end, a.end) for a, b in
                                                    zip(run.ticks, run.ticks[1:])]),
        "drain_s": drain_s,
        "reference_s": ref_s,
        "checked_requests": len(sample),
        "peak_bytes_in_use": device["memory_peak_bytes"],
        "trace": str(trace_dir) if trace_dir else None,
    }


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chip(chips: int) -> dict:
    """The device's peaks, or an error: no TPU, too few chips, or a device
    kind the peak table does not know."""
    import jax

    from chipbench.peaks import peak_for

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"error: no TPU (JAX platform {devs[0].platform!r}); "
                         "this benchmark has no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"error: the cell needs {chips} chips, JAX sees {len(devs)}")
    return peak_for(devs[0].device_kind)


def use_checkout_dirs() -> None:
    """Keep what JAX and the TPU runtime write inside the checkout: the
    persistent compilation cache at a fixed path, the runtime's logs beside
    the results. Call before the first look at the devices."""
    import os

    import jax

    logs = OUT / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_compilation_cache", True)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    spec = load_spec()
    cell = cell_of(spec, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"error: {ROOT} holds no program (src/repro missing)", file=sys.stderr)
        return 2
    sys.path.insert(1, str(ROOT / "src"))
    use_checkout_dirs()
    peak = chip(cell["chips"])
    result = run_cell(spec, cell, args.seed, args.seconds, bool(args.trace), t_start,
                      peak=peak)
    for name, c in result["check"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
