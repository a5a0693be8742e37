"""Decode step (``lm.decode_step``): the least time the chip could take for
the traced decode steps over the device time of the decode program, in
percent.

The least time of a step is the larger of the bytes it has to read (every
weight but the embedding table, and the keys and values of the valid
positions only) over HBM bandwidth and its FLOPs over the bf16 peak; at one
slot the bytes bound it by two orders of magnitude. The device time is read
from the profiler trace: of the programs that ran once per traced decode
step, the one that took the most device time, summed over its runs (the
eager argmax and slice that follow each step also run once per step, for
microseconds). A run or two at the edges of the trace may be missing, so a
program counts as once per step within a hundredth of the steps, and the
least time is that of the mean step times the program's runs."""

from chipbench import arith


def read(run):
    programs = run.trace.get("modules") if run.trace else None
    ticks = run.ticks[:run.traced_ticks]
    kv = [n for t in ticks for n in t.decode_kv]
    steps = sum(1 for t in ticks for ev in t.events if ev.phase == "decode")
    if not programs or not kv or run.peak is None:
        return None
    if len(kv) != steps:
        raise ValueError("decoded tokens and decode steps disagree")
    slack = steps // 100
    secs, runs = max(((s, n) for n, s in programs.values() if abs(n - steps) <= slack),
                     default=(0.0, 0))
    if secs <= 0:
        return None
    least = sum(max(arith.decode_bytes(run.model, n) / run.peak["hbm_bytes_per_s"],
                    arith.decode_flops(run.model, n) / run.peak["bf16_flops_per_s"])
                for n in kv)
    return 100.0 * least / steps * runs / secs
