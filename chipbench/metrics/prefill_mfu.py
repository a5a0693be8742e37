"""Prefill step (``lm.prefill``): the FLOPs of the prompts prefilled in the
traced ticks (the model family's ``prefill_flops``) over the device time of
every run of the ``engine_prefill`` program
(``jit_engine_prefill(<fingerprint>)``, one name per prompt length) times
the chip's bf16 peak, in percent. A run or so may be lost at the
edges of the trace, so the prefills' mean FLOPs are taken times the
program's runs; when the runs and the prefills differ by more than one, or
the program does not carry that name, it reads ``None``."""

from chipbench import families

PROGRAM = "jit_engine_prefill("


def read(run):
    programs = run.trace.get("modules") if run.trace else None
    if not programs or run.peak is None:
        return None
    prefills = [ev for t in run.ticks[:run.traced_ticks] for ev in t.events
                if ev.phase == "prefill"]
    runs = sum(n for name, (n, _) in programs.items() if name.startswith(PROGRAM))
    secs = sum(s for name, (_, s) in programs.items() if name.startswith(PROGRAM))
    if not prefills or secs <= 0 or abs(runs - len(prefills)) > 1:
        return None
    flops = sum(families.of(run.model).prefill_flops(run.model, ev.tokens) for ev in prefills)
    return 100.0 * flops / len(prefills) * runs / (secs * run.peak["bf16_flops_per_s"])
