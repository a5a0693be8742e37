"""Output tokens made visible inside the window, over the window's seconds
(host clock)."""


def read(run):
    n = sum(1 for r in run.recs for t in r.times if t <= run.seconds)
    return n / run.seconds if n else None
