"""Token mixers in prefill (the ``attn``, ``mamba``, ... sub-blocks of
``lm.prefill``): device microseconds of the prefill program's ops whose
scope names a mixer kind, and of the copies of the mixers' weights
(``chipbench/metrics/_scoped.py``), per prompt
token of the traced prefills. Every ``jit_engine_prefill(...)`` run counts
(one program per prompt length); a run or so may be lost at the edges of
the trace, so the mean run is divided by the prefills' mean prompt length,
and when the runs and the prefills differ by more than one it reads
``None``, as ``prefill_mfu`` does. ``None`` too when the program opens no
mixer scope."""

from chipbench.metrics._scoped import MIXERS, runs, seconds_in

PROGRAM = "jit_engine_prefill"


def read(run):
    if not run.trace:
        return None
    prefills = [ev.tokens for t in run.ticks[:run.traced_ticks] for ev in t.events
                if ev.phase == "prefill"]
    secs = seconds_in(run.trace, PROGRAM, MIXERS, run.model)
    n_runs, _ = runs(run.trace, PROGRAM)
    if not prefills or not secs or abs(n_runs - len(prefills)) > 1:
        return None
    return 1e6 * secs / n_runs / (sum(prefills) / len(prefills))
