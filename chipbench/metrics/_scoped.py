"""Device time of a program's ops by the layer kind whose
``jax.named_scope`` they ran under (the scope map of ``chipbench.scopes``).

The program opens one scope per sub-block, named after its kind
(``attn``, ``mamba``, ... for mixers; ``mlp``, ``moe`` for FFNs), so an op
belongs to a kind when a segment of its scope path is that name.

The compiler also moves some weights into the chip's on-core memory ahead of
their use, by asynchronous copies (``copy-start`` and ``copy-done``) that
carry no scope; the op that then uses the weight reads it from there, and
the wait for the copy's end is part of that layer's time. So a copy counts
for a kind when the weights of that shape (leading 1s dropped) belong to
that kind's sub-blocks alone, by the model family's layout: a position's
``<kind>/...`` leaves, its ``norm1`` with its mixer and its ``norm2`` with
its FFN. A copy of any other shape (caches, activations, a shape that two
kinds share) counts for none.

A program that opens no such scope reads 0 seconds; one that the trace does
not hold, or holds without a scope map, reads ``None``.
"""

from __future__ import annotations

import re
from collections import defaultdict

from chipbench import scopes, weights

MIXERS = frozenset({"attn", "attn_local", "mamba", "mlstm", "slstm"})
FFNS = frozenset({"mlp", "moe", "moe_dense"})
COPY = re.compile(r"^%copy-(?:start|done)[.\d]* \(?\w+\[([\d,]*)\]")


def runs(trace: dict, program: str) -> tuple[int, float]:
    """(runs, device seconds) of every compiled ``program`` (a name without
    its fingerprint) in the trace's ``XLA Modules``."""
    found = [v for name, v in trace.get("modules", {}).items()
             if name.split("(")[0] == program]
    return sum(n for n, _ in found), sum(s for _, s in found)


def _squeeze(shape) -> tuple:
    shape = tuple(shape)
    while shape and shape[0] == 1:
        shape = shape[1:]
    return shape


def weight_kinds(m: dict) -> dict[tuple, set]:
    """Per weight shape, leading 1s dropped (one superblock's leaf, and the
    leaf stacked over superblocks), the layer kinds that hold one."""
    out = defaultdict(set)
    for path, (shape, stacked, _) in weights.layout(m).items():
        parts = path.split("/")
        if parts[0] != "blocks":
            continue  # the embedding, the head and the final norm
        at = int(parts[1]) if parts[1].isdigit() else 0
        mixer, ffn = m["layers"][at]
        name = parts[2] if parts[1].isdigit() else parts[1]
        kind = {"norm1": mixer, "norm2": ffn}.get(name, name)
        out[_squeeze(shape)].add(kind)
        if stacked:
            out[_squeeze((m["num_superblocks"], *shape))].add(kind)
    return out


def _copy_kinds(op: str, by_shape: dict) -> set:
    hit = COPY.match(op)
    if not hit:
        return set()
    return by_shape.get(_squeeze(int(x) for x in hit.group(1).split(",") if x), set())


def seconds_in(trace: dict, program: str, kinds: frozenset, m: dict) -> float | None:
    """Device seconds of ``program``'s ops whose scope names one of
    ``kinds``, and of its weight copies that belong to those kinds alone."""
    by_shape = weight_kinds(m)
    total, found = 0.0, False
    for run_name, ops in trace.get("op_seconds", {}).items():
        if run_name.split("(")[0] != program:
            continue
        mapped = scopes.scope_map(trace, run_name)
        if mapped is None:
            continue
        found = True
        for op, t in ops.items():
            named = set(re.split("[/;]", mapped.get(op) or ""))
            copied = _copy_kinds(op, by_shape)
            if kinds & named or (copied and copied <= kinds):
                total += t
    return total if found else None
