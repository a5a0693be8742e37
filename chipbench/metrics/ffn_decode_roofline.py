"""Feed-forward layers in the decode step (``mlp`` and ``moe`` sub-blocks
of ``lm.decode_step``): the least time the chip could take for them over
their device time in the traced decode steps, in percent.

The least time of a step is the larger of the FFN's bytes over HBM
bandwidth and its FLOPs over the bf16 peak. Both come from the model
family's weight layout: the ``mlp/`` leaves and the router read whole,
and each expert layer's ``moe/`` expert leaves in the share of its held
experts that the tick's counters (``expert_tokens``) say the step hit; the
FLOPs are twice the MLP's and the router's elements per token and twice an
expert's per (token, choice) pair routed to it. The device time is that of
the ops of every ``jit_engine_decode(...)`` run whose scope names ``mlp``
or ``moe``, and of the copies that move those layers' weights into the
chip's on-core memory ahead of their use (``chipbench/metrics/_scoped.py``:
the copied bytes are read there, not by the layer's own ops, and the
layer waits for them); a run or so may be missing
at the edges of the trace, so the least time is that of the mean step
times the program's runs. ``None`` when the program opens no such scope.
"""

from __future__ import annotations

import re

import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.metrics._scoped import FFNS, runs, seconds_in

PROGRAM = "jit_engine_decode"
EXPERT = re.compile(r"^blocks/(?:(\d+)/)?moe/w[igo]$")


def ffn_cost(m: dict, counters: dict) -> tuple[float, float]:
    """(bytes, FLOPs) of one decode step's FFN sub-blocks, one token."""
    n = m["num_superblocks"]
    b = jnp.dtype(m["dtype"]).itemsize
    dense = experts_bytes = experts_flops = 0.0
    expert_leaves = {}  # superblock position -> elements of all its held experts
    for path, (shape, stacked, _) in weights.layout(m).items():
        size = int(np.prod(shape)) * (n if stacked else 1)
        hit = EXPERT.match(path)
        if hit:
            pos = int(hit.group(1) or 0)
            expert_leaves[pos] = expert_leaves.get(pos, 0) + int(np.prod(shape))
        elif "/mlp/" in path or path.endswith("/moe/router"):
            dense += size
    if expert_leaves:
        routed = np.asarray(counters["expert_tokens"]).reshape(n, len(expert_leaves), -1)
        held = routed.shape[-1]
        for j, pos in enumerate(sorted(expert_leaves)):
            per_expert = expert_leaves[pos] / held
            experts_bytes += per_expert * int((routed[:, j] > 0).sum())
            experts_flops += 2.0 * per_expert * int(routed[:, j].sum())
    return b * (dense + experts_bytes), 2.0 * dense + experts_flops


def read(run):
    trace = run.trace
    if not trace or run.peak is None:
        return None
    secs = seconds_in(trace, PROGRAM, FFNS, run.model)
    n_runs, _ = runs(trace, PROGRAM)
    counters = [t.counters for t in run.ticks[:run.traced_ticks] for _ in t.decode_kv]
    if not secs or not n_runs or not counters:
        return None
    least = 0.0
    for c in counters:
        nbytes, flops = ffn_cost(run.model, c)
        least += max(nbytes / run.peak["hbm_bytes_per_s"], flops / run.peak["bf16_flops_per_s"])
    return 100.0 * least / len(counters) * n_runs / secs
