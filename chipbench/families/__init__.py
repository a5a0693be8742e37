"""Model families: everything that depends on a model's layer kinds, one
module per family, found by the ``family`` of the configuration's model
block (``chipbench/families/<family>.py``), as metric readers are found by
their metric's name.

A family module provides:

``program_config(m)``
    the program's ``repro.configs.base.ModelConfig`` for the model block ``m``;
``layout(m)``
    ``path -> (shape of one superblock's leaf, stacked over superblocks,
    std)`` for every leaf of the served parameter tree (``weights.py``).
    A block path ``blocks/<i>/...`` lies at superblock position ``i``; one
    without a position (``blocks/attn/wq``) lies at position 0;
``hidden(m, seed, rows, top, fp8)``
    the plain reference's final hidden states of each token row in float32
    at ``Precision.HIGHEST``, with weights from ``weights.layer_f32`` and
    ``top`` from ``weights.top_f32``; ``fp8`` is the control;
``prefill_flops(m, L)``, ``decode_flops(m, kv_len, counters)``, ``decode_bytes(m, kv_len, counters)``
    the operations and bytes the algorithm needs, from shapes and from the
    counters of the tick that decoded the token;
``tick_counters(engine)``
    what the program counts in one ``Engine.tick``, read after it returns
    (kept on ``harness.Tick.counters``).
"""

from __future__ import annotations

import importlib


def of(m: dict):
    """The family module of the model block ``m``."""
    return importlib.import_module(f"{__name__}.{m['family']}")
