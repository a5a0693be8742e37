"""jit'd wrapper for the batched Lindley-recursion kernel."""

from __future__ import annotations

from functools import partial

import jax

from .lindley_scan import lindley_scan_pallas
from .ref import lindley_scan_reference

__all__ = ["lindley_scan"]


@partial(jax.jit, static_argnames=("impl", "blk_b", "blk_t"))
def lindley_scan(arrivals, services, *, impl: str = "pallas", blk_b: int = 128, blk_t: int = 512):
    if impl == "xla":
        return lindley_scan_reference(arrivals, services)
    return lindley_scan_pallas(
        arrivals, services, blk_b=blk_b, blk_t=blk_t, interpret=(impl == "interpret")
    )
