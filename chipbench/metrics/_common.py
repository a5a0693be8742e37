"""Shared arithmetic of the metric readers."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (linear interpolation), or ``None`` if empty."""
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


def window_gaps(run) -> list[float]:
    """Gaps between consecutive tokens of a request, seconds, for every gap
    whose later token became visible inside the window."""
    gaps = []
    for r in run.recs:
        t = r.times
        gaps += [b - a for a, b in zip(t, t[1:]) if b <= run.seconds]
    return gaps


def service(run, phase: str) -> list:
    return [ev for tick in run.ticks for ev in tick.events if ev.phase == phase]
