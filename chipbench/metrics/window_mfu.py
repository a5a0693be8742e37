"""Whole model step: the FLOPs of every prompt token prefilled and every
token decoded in the window's ticks, from shapes and tick counters (the
model family's arithmetic), over the window's seconds times the chip's
bf16 peak, in percent."""

from chipbench import families
from chipbench.metrics._common import service


def read(run):
    if run.peak is None or not run.ticks:
        return None
    family = families.of(run.model)
    flops = sum(family.prefill_flops(run.model, ev.tokens) for ev in service(run, "prefill"))
    flops += sum(family.decode_flops(run.model, n, t.counters)
                 for t in run.ticks for n in t.decode_kv)
    return 100.0 * flops / (run.seconds * run.peak["bf16_flops_per_s"]) if flops else None
