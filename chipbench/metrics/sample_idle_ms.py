"""Engine host loop, decode step: device idle inside ``engine.sample``
under ``engine.decode`` (the eager ``argmax`` and its copy to the host) per
decode step of the traced window, in milliseconds
(``chipbench.engine_spans``)."""

from chipbench import engine_spans


def read(run):
    return engine_spans.idle_ms_per(run, "engine.decode/engine.sample", "engine.decode")
