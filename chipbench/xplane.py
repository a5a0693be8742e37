"""Reduce a JAX profiler trace to device busy time, idle gaps and top ops.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named after the program (``XLA Modules``) that
ran them; busy time is the union of their intervals, so nested events (a
loop and the ops of its body) count once, and the top ops are ranked by
self time. Each run of a compiled program is one event of the ``XLA
Modules`` line, named with its fingerprint, so two programs do not share a
name; each op's self time is also kept per program run name
(``op_seconds``), for readers that sum the ops under a source scope
(``chipbench/scopes.py``). Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
events, found by name on the host plane. All times are nanoseconds on the
profiler's clock.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("tick", "generate")


def newest_xplane(trace_dir: Path) -> Path | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def op_key(op: str) -> str:
    """``<op> <result type>``: the HLO instruction's name and the type it
    returns, from the instruction's text (``%fusion.9 = bf16[64]{0} ...``)."""
    head, _, rest = op.partition(" = ")
    return f"{head} {rest.split('{')[0].split(' ')[0]}".rstrip()


def short_name(op: str, module: str) -> str:
    """``<module>/<op> <result type>``: the module's name without its hash,
    then :func:`op_key`."""
    return f"{module.split('(')[0]}/{op_key(op)}"


def read_events(path: Path, spans=HOST_SPANS):
    """(device ops per device plane, program runs per device plane, host
    spans named in ``spans``), each a list of ``(name, start_ns, end_ns)``;
    each op is named by :func:`short_name`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: dict[str, list] = {}
    programs: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            lines = {line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events] for line in plane.lines}
            modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
            starts = [s for _, s, _ in modules]
            ops = []
            for name, s, e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, s) - 1
                module = modules[i][0] if i >= 0 and modules[i][2] >= e else "?"
                ops.append((short_name(name, module), s, e))
            devices[plane.name] = ops
            programs[plane.name] = modules
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in spans]
    return devices, programs, host


def module_totals(runs) -> dict:
    """Per program name: ``[runs, seconds]`` over the whole trace."""
    out: dict = defaultdict(lambda: [0, 0.0])
    for name, s, e in runs:
        out[name][0] += 1
        out[name][1] += (e - s) * 1e-9
    return dict(out)


def union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(ops, lo, hi) -> float:
    return float(sum(e - s for s, e in union(clip([(s, e) for _, s, e in ops], lo, hi))))


def _self_ns(ops, lo, hi):
    """``(name, start, self ns)`` per op inside ``[lo, hi)``: its time less
    the time of the ops nested in it (a loop holds its body's ops on the
    same line)."""
    out = []
    stack: list[list] = []  # [name, end, self_ns, start]
    for name, s, e in sorted(clip_named(ops, lo, hi), key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            out.append(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s, s])
    out += stack
    return [(name, s, self_ns) for name, _, self_ns, s in out]


def self_times(ops, lo, hi):
    """``(name, seconds)`` per op inside ``[lo, hi)``, less the time of the ops
    nested in it."""
    return [(name, self_ns * 1e-9) for name, _, self_ns in _self_ns(ops, lo, hi)]


def op_seconds(ops, runs) -> dict:
    """Per program run name (``jit_engine_decode(<fingerprint>)``, the
    ``XLA Modules`` event that holds the op's start), each op's self seconds
    over the whole trace, keyed by :func:`op_key`; ops that no run holds
    are under ``?``."""
    runs = sorted(runs, key=lambda r: r[1])
    starts = [s for _, s, _ in runs]
    out: dict = defaultdict(lambda: defaultdict(float))
    for name, s, self_ns in _self_ns(ops, float("-inf"), float("inf")):
        i = bisect.bisect_right(starts, s) - 1
        program = runs[i][0] if i >= 0 and runs[i][2] > s else "?"
        out[program][name.split("/", 1)[1]] += self_ns * 1e-9
    return {p: dict(v) for p, v in out.items()}


def clip_named(ops, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]


def top_ops(ops, lo, hi, k: int = 10):
    """The ``k`` op names with most self time inside ``[lo, hi)``, seconds."""
    per = defaultdict(float)
    for name, t in self_times(ops, lo, hi):
        per[name] += t
    return [[n, t] for n, t in sorted(per.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(ops, host, lo, hi, k: int = 10):
    """The ``k`` longest gaps with no device op inside ``[lo, hi)``, each named
    by the host span that covers most of it (``host`` when none does)."""
    busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:k]:
        cover = defaultdict(float)
        for name, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[name] += ov
        label = max(cover, key=cover.get) if cover else "host"
        named.append([label, (e - s) * 1e-9])
    return named


def reduce(devices: dict, host, lo, hi, programs: dict | None = None) -> dict:
    """Busy seconds averaged over the device planes, and the breakdown of the
    first plane, over the window ``[lo, hi)``. With ``programs`` (the runs
    that :func:`read_events` gives per plane) also the first plane's
    ``op_seconds`` and ``modules`` (:func:`module_totals`), over the whole
    trace."""
    if not devices or not any(devices.values()):
        return {}
    busy = [busy_ns(ops, lo, hi) for ops in devices.values()]
    plane, first = next(iter(devices.items()))
    out = {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": top_ops(first, lo, hi),
        "idle_gaps": idle_gaps(first, host, lo, hi),
    }
    if programs is not None:
        out["op_seconds"] = op_seconds(first, programs.get(plane, []))
        out["modules"] = module_totals(programs.get(plane, []))
    return out
