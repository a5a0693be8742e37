"""Whole model step: the FLOPs of every prompt token prefilled and every
token decoded in the window's ticks, from shapes, over the window's seconds
times the chip's bf16 peak, in percent."""

from chipbench import arith
from chipbench.metrics._common import service


def read(run):
    if run.peak is None or not run.ticks:
        return None
    flops = sum(arith.prefill_flops(run.model, ev.tokens) for ev in service(run, "prefill"))
    flops += sum(arith.decode_flops(run.model, n) for t in run.ticks for n in t.decode_kv)
    return 100.0 * flops / (run.seconds * run.peak["bf16_flops_per_s"]) if flops else None
