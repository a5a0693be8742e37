"""jit'd wrapper for the staggered-decision scan kernel."""

from __future__ import annotations

from functools import partial

import jax

from .decision_scan import decision_scan_pallas
from .ref import decision_scan_reference

__all__ = ["decision_scan"]


@partial(jax.jit,
         static_argnames=("impl", "hysteresis", "stagger", "blk_n", "blk_t"))
def decision_scan(costs, cohort, *, hysteresis: float = 0.0, stagger: int = 1,
                  impl: str = "pallas", blk_n: int = 128, blk_t: int = 128):
    if impl == "xla":
        return decision_scan_reference(
            costs, cohort, hysteresis=hysteresis, stagger=stagger)
    return decision_scan_pallas(
        costs, cohort, hysteresis=hysteresis, stagger=stagger,
        blk_n=blk_n, blk_t=blk_t, interpret=(impl == "interpret"))
