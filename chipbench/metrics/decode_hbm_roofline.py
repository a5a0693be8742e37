"""Decode step (``lm.decode_step``): the least time the chip could take for
the traced decode steps over the device time of the decode program, in
percent.

The least time of a step is the larger of the bytes it has to read over
HBM bandwidth and its FLOPs over the bf16 peak, both from the model
family's arithmetic and the counters of the tick that decoded the token
(dense: every weight but the embedding table, and the keys and values of
the valid positions only; at one slot the bytes bound it by two orders of
magnitude). The device time is read from the profiler trace: of the
programs that ran once per traced decode step, the one that took the most
device time, summed over its runs (the eager argmax and slice that follow
each step also run once per step, for microseconds). A run or two at the edges of the trace may be missing, so a
program counts as once per step within a hundredth of the steps, and the
least time is that of the mean step times the program's runs."""

from chipbench import families


def read(run):
    programs = run.trace.get("modules") if run.trace else None
    ticks = run.ticks[:run.traced_ticks]
    kv = [(n, t.counters) for t in ticks for n in t.decode_kv]
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
    family = families.of(run.model)
    least = sum(max(family.decode_bytes(run.model, n, c) / run.peak["hbm_bytes_per_s"],
                    family.decode_flops(run.model, n, c) / run.peak["bf16_flops_per_s"])
                for n, c in kv)
    return 100.0 * least / steps * runs / secs
