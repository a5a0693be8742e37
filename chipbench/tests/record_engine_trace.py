"""Record the small engine trace that ``test_engine_spans.py`` reads.

Run once on a machine with a TPU, from the root of a checkout:

    python3 chipbench/tests/record_engine_trace.py

It serves a two-layer model at small widths through ``Engine`` inside the
harness's own host spans (``generate`` around the load, ``tick`` around
each ``Engine.tick``), as a closed loop with a few admissions and a score of
decode steps, and prints the engine spans and programs the trace holds.
The profiler's file holds far more than the reduction reads (compiled
programs' HLO, every host thread's events, op statistics), so
:func:`shrink` keeps the device plane's ``XLA Ops`` and ``XLA Modules``
lines, the host spans of the harness and the engine, the runtime's
``tpu::System::Execute`` events that bound the device clock's offset, and
the profile's start time, drops every event's statistics, and writes the result, under
200 KB, to ``chipbench/tests/data/engine.xplane.pb``.
"""

import shutil
import sys
import time
from collections import Counter
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[0] = str(HERE.parents[1])
sys.path.insert(1, str(HERE.parents[1] / "src"))

MODEL = dict(name="small", family="dense", d_model=64, num_heads=4, num_kv_heads=4,
             head_dim=16, d_ff=96, vocab_size=512, num_superblocks=2, layers=[["attn", "mlp"]],
             gated_mlp=True, mlp_act="silu", rope_theta=10000.0, norm_eps=1e-6, dtype="bfloat16")
SEED = 2**33 + 7
TICKS = 24
DST = HERE / "data" / "engine.xplane.pb"
DEVICE_LINES = ("XLA Ops", "XLA Modules")


# -- a protobuf message as raw fields: the trace is an ``XSpace`` message --


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _enc(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _fields(buf: bytes):
    """``(field number, raw bytes of the field, payload)`` of each field."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        else:
            n = {1: 8, 5: 4}[wire]
            val, i = buf[i:i + n], i + n
        yield key >> 3, buf[start:i], val


def _sub(field: int, payload: bytes) -> bytes:
    return _enc(field << 3 | 2) + _enc(len(payload)) + payload


def _name(msg: bytes, field: int = 2) -> str:
    return next((v.decode() for f, _, v in _fields(msg) if f == field), "")


def _meta_entry(mid: int, name: str) -> bytes:
    """A ``map<int64, XEventMetadata>`` entry that holds only id and name."""
    value = _enc(1 << 3) + _enc(mid) + _sub(2, name.encode())
    return _sub(4, _enc(1 << 3) + _enc(mid) + _sub(2, value))


def _plane(plane: bytes, keep_line, keep_event) -> bytes:
    """An ``XPlane`` with its id and name, the kept lines (field 3) and
    events (line field 4, less their statistics), and the metadata (field
    4) of the kept events, each reduced to its id and name."""
    meta = {}  # metadata id -> name
    for f, _, v in _fields(plane):
        if f == 4:
            entry = {g: w for g, _, w in _fields(v)}
            meta[entry.get(1, 0)] = _name(entry.get(2, b""))
    out, used = [], set()
    for f, raw, v in _fields(plane):
        if f in (1, 2):
            out.append(raw)
        elif f == 3 and keep_line(_name(v)):
            line, events = [], []
            for g, graw, w in _fields(v):
                if g != 4:
                    line.append(graw)
                    continue
                ev = list(_fields(w))
                mid = next((x for h, _, x in ev if h == 1), 0)
                if keep_event(meta.get(mid, "")):
                    used.add(mid)
                    events.append(_sub(4, b"".join(r for h, r, _ in ev if h != 4)))
            if events:
                out.append(_sub(3, b"".join(line + events)))
    out += [_meta_entry(mid, meta[mid]) for mid in sorted(used)]
    return b"".join(out)


def shrink(src: Path, dst: Path) -> Path:
    """Keep what ``xplane.read_events`` and ``engine_spans`` read: the first
    TPU plane's ``XLA Ops`` and ``XLA Modules`` lines, the host plane's
    harness, engine and runtime spans, and the ``Task Environment`` plane."""
    from chipbench import engine_spans, xplane

    host_names = set(xplane.HOST_SPANS + engine_spans.SPANS + engine_spans.RUNTIME)
    out = []
    for f, raw, v in _fields(Path(src).read_bytes()):
        if f != 1:
            out.append(raw)
            continue
        name = _name(v)
        if name == "/device:TPU:0":
            out.append(_sub(1, _plane(v, lambda n: n in DEVICE_LINES, lambda n: True)))
        elif name.startswith("/host:") and name != "/host:metadata":
            out.append(_sub(1, _plane(v, lambda n: True, lambda n: n in host_names)))
        elif name == "Task Environment":
            out.append(raw)
    dst.write_bytes(b"".join(out))
    return dst


def record(ticks: int = TICKS, dst: Path = DST) -> Path:
    import jax

    from chipbench import engine_spans, harness, xplane
    from chipbench import traffic as T

    mix = T.Mix(name="small", prompt_lens=(8, 24), prompt_weights=(1, 1), output_mean=4,
                output_cap=8)
    engine = harness.build_engine(MODEL, SEED)
    harness.warm_up(engine, mix)
    load = harness.Load(mix, SEED, MODEL["vocab_size"])
    recs, live, done = [], [], []
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0
    tmp = dst.parent / f"raw_{dst.stem}"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp))
    for _ in range(ticks):
        with harness._annotate("generate"):
            load.feed(engine, recs, live)
        harness._tick(engine, clock, live, done)
    jax.profiler.stop_trace()
    shrink(xplane.newest_xplane(tmp), dst)
    shutil.rmtree(tmp)
    names = xplane.HOST_SPANS + engine_spans.SPANS + engine_spans.RUNTIME
    devices, modules, host = xplane.read_events(dst, spans=names)
    print(Counter(n for n, _, _ in host))
    print({k: xplane.module_totals(v) for k, v in modules.items()})
    print(engine_spans.split_file(dst))
    print(f"{dst}: {dst.stat().st_size} bytes")
    return dst


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 1
    record()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
