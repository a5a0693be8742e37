"""Device idle of a traced run, split by the engine span the host was in.

The program names its served loop's phases with ``TraceAnnotation`` spans
(``repro.serving.engine``): ``engine.admit`` per admitted request and
``engine.decode`` per decode step, each holding ``engine.launch``,
``engine.wait`` and ``engine.sample``. This module reads the newest
``.xplane.pb`` of a traced run, takes the window that ``xplane.reduce``
takes (the first to the last harness span), and gives each nanosecond in
which no ``XLA Ops`` event runs on the first device plane to the innermost
engine span that covers it: the covering span that started last. A child
is labelled by its parent, as ``engine.decode/engine.launch``; idle that
no engine span covers is ``outside``. The labels' idle sums to the
window's idle. A program without the spans gives every idle nanosecond to
``outside`` and counts no admission and no decode step.

The profiler writes the device's events on a clock that can sit a
fraction of a millisecond to two milliseconds off the host's, differently
in each session. The runtime's own host events bound that offset: each
program run starts after the ``tpu::System::Execute`` that issued it and
ends before the ``tpu::System::Execute=>Done`` that saw it finish (the
three pair in order, one device stream). The device's events are moved by
the middle of that bound, taken over each ``CHUNK`` consecutive runs,
before idle is given to host spans; a trace in which no pairing fits
gives no split.
"""

from __future__ import annotations

import bisect
import functools
import heapq
from collections import defaultdict
from pathlib import Path

from chipbench import harness, xplane

PARENTS = ("engine.admit", "engine.decode")
CHILDREN = ("engine.launch", "engine.wait", "engine.sample")
SPANS = PARENTS + CHILDREN
OUTSIDE = "outside"
RUNTIME = ("tpu::System::Execute", "tpu::System::Execute=>Done")
EDGE = 2  # runtime events a trace may lose at its ends, of each kind
CHUNK = 128  # consecutive program runs that share one clock offset


def idle_intervals(ops, lo, hi):
    """The complement of the union of ``ops`` inside ``[lo, hi)``."""
    out, t = [], lo
    for s, e in xplane.union(xplane.clip([(s, e) for _, s, e in ops], lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def labels(spans):
    """Each span's label: a child under the parent whose interval holds its
    start (the latest such parent), a parent or an orphan by its own name."""
    parents = sorted((s, e) for n, s, e in spans if n in PARENTS)
    names = {(s, e): n for n, s, e in spans if n in PARENTS}
    starts = [s for s, _ in parents]
    out = []
    for n, s, e in spans:
        if n in CHILDREN:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and parents[i][1] >= e:
                n = f"{names[parents[i]]}/{n}"
        out.append(n)
    return out


def innermost(spans, names):
    """The time the spans cover, as disjoint ``(start, end, name)`` pieces,
    each named after the covering span that started last."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    heap: list = []  # (-start, -index, end): the latest start on top
    pieces, k = [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(order) and spans[order[k]][1] <= a:
            i = order[k]
            heapq.heappush(heap, (-spans[i][1], -i, spans[i][2]))
            k += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        if heap:
            name = names[-heap[0][1]]
            if pieces and pieces[-1][2] == name and pieces[-1][1] == a:
                pieces[-1] = (pieces[-1][0], b, name)
            else:
                pieces.append((a, b, name))
    return pieces


def attribute(idle, pieces) -> dict:
    """Idle nanoseconds per piece name; what no piece covers is ``outside``.
    Both lists are sorted and disjoint."""
    out: dict = defaultdict(float)
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
            if ov > 0:
                out[pieces[k][2]] += ov
                covered += ov
            k += 1
        out[OUTSIDE] += (e - s) - covered
    return dict(out)


def _chunks(issued, seen, runs, ji: int, jd: int):
    """``(first run's start, lo, hi)`` per ``CHUNK`` consecutive runs, pairing
    ``issued[k]`` with ``runs[k + ji]`` and ``seen[k]`` with ``runs[k + jd]``:
    the offset lies at or above each pair's ``Execute`` start less its run's
    start, and at or below each pair's ``Done`` end less its run's end."""
    n = len(runs)
    lo, hi = [None] * n, [None] * n
    for k, x in enumerate(issued):
        if 0 <= k + ji < n:
            lo[k + ji] = x - runs[k + ji][0]
    for k, y in enumerate(seen):
        if 0 <= k + jd < n:
            hi[k + jd] = y - runs[k + jd][1]
    out = []
    for c in range(0, n, CHUNK):
        ls = [v for v in lo[c:c + CHUNK] if v is not None]
        hs = [v for v in hi[c:c + CHUNK] if v is not None]
        if not ls or not hs:
            return None
        out.append((runs[c][0], max(ls), min(hs)))
    return out


def clock_offsets(runs, host) -> list | None:
    """``(device time, offset)`` from which each offset holds: nanoseconds to
    add to the device's times to put them on the host's clock. Program runs
    (``XLA Modules``) pair in order with the runtime's
    ``tpu::System::Execute`` (which starts before the run) and
    ``tpu::System::Execute=>Done`` (which ends after it); each ``CHUNK``
    consecutive runs take the middle of their bound, since the offset moves
    by up to ~0.2 ms in a trace's first second. A trace may lose up to
    ``EDGE`` events of a kind at its ends; then each pairing within the
    difference of the counts is tried and the narrowest that every chunk
    fits is taken (pairing with an earlier ``Execute`` or a later ``Done``
    only loosens a bound; a wrong pairing the other way leaves a chunk with
    none). ``None`` when no pairing fits."""
    issued = sorted(s for n, s, _ in host if n == RUNTIME[0])
    seen = sorted(e for n, _, e in host if n == RUNTIME[1])
    runs = sorted((s, e) for _, s, e in runs)
    n = len(runs)
    di, dd = abs(len(issued) - n), abs(len(seen) - n)
    if not (n and issued and seen) or max(di, dd) > EDGE:
        return None
    best = None
    for ji in range(-di, di + 1):
        for jd in range(-dd, dd + 1):
            chunks = _chunks(issued, seen, runs, ji, jd)
            if chunks is None or any(lo > hi for _, lo, hi in chunks):
                continue
            width = sum(hi - lo for _, lo, hi in chunks)
            if best is None or width < best[0]:
                best = (width, chunks)
    return None if best is None else [(t, (lo + hi) / 2) for t, lo, hi in best[1]]


def to_host(events, offsets) -> list:
    """``(name, start, end)`` device events moved onto the host's clock by the
    offset of the chunk each starts in (the first chunk's before it)."""
    starts = [t for t, _ in offsets]
    out = []
    for n, s, e in events:
        d = offsets[max(bisect.bisect_right(starts, s) - 1, 0)][1]
        out.append((n, s + d, e + d))
    return out


def split_events(devices: dict, programs: dict, host) -> dict | None:
    """The idle split of one trace's events (``xplane.read_events`` with the
    harness's, the engine's and the runtime's span names)."""
    marks = [(s, e) for n, s, e in host if n in xplane.HOST_SPANS]
    if not devices or not marks:
        return None
    offsets = clock_offsets(next(iter(programs.values()), []), host)
    if offsets is None:
        return None
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    ops = to_host(next(iter(devices.values())), offsets)
    engine = sorted(((n, s, e) for n, s, e in host if n in SPANS and s >= lo and e <= hi),
                    key=lambda x: (x[1], -x[2]))
    idle = idle_intervals(ops, lo, hi)
    return {
        "window_ns": hi - lo,
        "offsets_ns": offsets,
        "idle_ns": attribute(idle, innermost(engine, labels(engine))),
        "total_idle_ns": float(sum(e - s for s, e in idle)),
        "count": {p: sum(1 for n, _, _ in engine if n == p) for p in PARENTS},
    }


@functools.lru_cache(maxsize=4)
def _split_file(path: str, mtime_ns: int) -> dict | None:
    devices, programs, host = xplane.read_events(Path(path),
                                                 spans=xplane.HOST_SPANS + SPANS + RUNTIME)
    return split_events(devices, programs, host)


def split_file(path: Path) -> dict | None:
    """:func:`split_events` of the file, computed once per file."""
    return _split_file(str(path), Path(path).stat().st_mtime_ns)


def for_run(run) -> dict | None:
    """The split of a traced run's newest trace, under the harness's own
    ``OUT/trace/<cell>``; ``None`` when the run holds no trace."""
    if not run.trace:
        return None
    path = xplane.newest_xplane(harness.OUT / "trace" / run.cell)
    return split_file(path) if path is not None else None


def idle_ms_per(run, label_prefix: str, parent: str) -> float | None:
    """Idle milliseconds under labels that start with ``label_prefix``, per
    ``parent`` span in the window; ``None`` without a trace or a span."""
    split = for_run(run)
    if split is None or not split["count"][parent]:
        return None
    ns = sum(v for k, v in split["idle_ns"].items() if k.startswith(label_prefix))
    return ns * 1e-6 / split["count"][parent]
