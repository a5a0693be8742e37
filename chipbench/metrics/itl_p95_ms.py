"""95th percentile of every gap between consecutive tokens of a request,
across all requests, for gaps that closed inside the window (host clock)."""

from chipbench.metrics._common import percentile, window_gaps


def read(run):
    return percentile([g * 1e3 for g in window_gaps(run)], 95)
