"""Engine host loop, decode step: device idle inside ``engine.launch``
under ``engine.decode`` (the uploads of the last token and position and the
dispatch of the decode program) per decode step of the traced window, in
milliseconds (``chipbench.engine_spans``)."""

from chipbench import engine_spans


def read(run):
    return engine_spans.idle_ms_per(run, "engine.decode/engine.launch", "engine.decode")
