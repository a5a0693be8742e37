"""Seconds from process start to the start of the measured window: loading,
weights, engine, warm-up and any compilation (host clock)."""


def read(run):
    return run.setup_s
