"""One reader per metric, found by the metric's name in BENCHMARK.json.

``read(run)`` takes a ``chipbench.harness.Run`` and returns the metric's
value, or ``None`` when the run holds nothing for it to read. A share of a
roofline or of a peak is never reported as 0 for want of data.
"""
