"""Engine host loop, admission: device idle inside the ``engine.admit``
spans (any of their children included) over the admissions in the traced
window, in milliseconds (``chipbench.engine_spans``). ``None`` when the
window holds no admission."""

from chipbench import engine_spans


def read(run):
    return engine_spans.idle_ms_per(run, "engine.admit", "engine.admit")
