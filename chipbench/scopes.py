"""Where each device op of a traced program comes from in the program's
source: the ``jax.named_scope`` path of its HLO instruction.

The profiler names a device op after its HLO instruction's text and keeps
none of its metadata. The optimized HLO of the compiled program carries,
on each instruction, ``metadata={op_name="jit(engine_decode)/..."}``: the
scopes it was traced under, then the primitive. After a traced window the
harness lowers each program the window drove on the engine's own weights and
cache and on the shapes of the ids it is called with (the executable comes
back from JAX's cache in memory: nothing compiles) and keeps,
per program name, one map from :func:`chipbench.xplane.op_key` to that
scope per compiled program (prefill compiles one program per prompt
length). An instruction without metadata, such as a copy of a parameter,
gets the program's own outermost scope.

A reader sums an op's device time (``xplane.op_seconds``) under a scope with
:func:`seconds_under`, with no change to the trace reduction.
"""

from __future__ import annotations

import re
from collections import Counter, defaultdict

from chipbench import xplane

MODULE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%\S+ = .*)$")
OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="([^"]*)"')


def hlo_scopes(text: str) -> tuple[str, dict]:
    """``(program name, {op_key: scope})`` of one compiled program's HLO
    text, every instruction of every computation in it."""
    program = MODULE.search(text).group(1)
    found = {}
    for line in text.splitlines():
        ins = INSTRUCTION.match(line)
        if ins:
            name = OP_NAME.search(ins.group(1))
            found[xplane.op_key(ins.group(1))] = name.group(1) if name else None
    tops = Counter(s.split("/")[0] for s in found.values() if s)
    root = tops.most_common(1)[0][0] if tops else program
    return program, {k: s or root for k, s in found.items()}


def engine_programs(engine, mix) -> list:
    """``(jitted function, arguments)`` of each program that a window of
    ``mix`` drives: decode, and prefill at each prompt length."""
    import jax
    import jax.numpy as jnp

    ids = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    out = [(engine._decode, (engine.params, ids(engine.sc.slots, 1), ids(), engine.caches))]
    out += [(engine._prefill, (engine.params, ids(1, L))) for L in sorted(set(mix.prompt_lens))]
    return out


def program_scopes(engine, mix) -> dict:
    """Per program name, the :func:`hlo_scopes` map of each of its compiled
    programs."""
    out = defaultdict(list)
    for fn, args in engine_programs(engine, mix):
        program, scopes = hlo_scopes(fn.lower(*args).compile().as_text())
        out[program].append(scopes)
    return dict(out)


def scope_map(trace: dict, run_name: str) -> dict | None:
    """The scope map of the compiled program behind one traced program run
    name: of the maps of that program's name, the one that holds the most of
    the run's ops."""
    ops = trace.get("op_seconds", {}).get(run_name, {})
    maps = trace.get("scopes", {}).get(run_name.split("(")[0], [])
    return max(maps, key=lambda m: sum(k in m for k in ops), default=None)


def seconds_under(trace: dict, program: str, prefix: str) -> float | None:
    """Device seconds of the ops of every run of ``program`` (a name without
    its fingerprint) whose scope starts with ``prefix``; ``None`` when the
    trace holds no run of it or no scope map for it."""
    total, found = 0.0, False
    for run_name, ops in trace.get("op_seconds", {}).items():
        if run_name.split("(")[0] != program:
            continue
        scopes = scope_map(trace, run_name)
        if scopes is None:
            continue
        found = True
        total += sum(t for k, t in ops.items() if scopes.get(k, "").startswith(prefix))
    return total if found else None


def coverage(trace: dict) -> dict:
    """Per traced program run name: its runs and device seconds
    (``XLA Modules``), the self seconds of its ops, and of those the seconds
    of ops found in its scope map and of ops whose instruction names a scope
    below the program's outermost one."""
    out = {}
    for run_name, (runs, secs) in trace.get("modules", {}).items():
        ops = trace.get("op_seconds", {}).get(run_name, {})
        scopes = scope_map(trace, run_name) or {}
        root = run_name.split("(")[0]
        out[run_name] = {
            "runs": runs, "module_s": secs, "ops_s": sum(ops.values()),
            "mapped_s": sum(t for k, t in ops.items() if k in scopes),
            "scoped_s": sum(t for k, t in ops.items() if "/" in scopes.get(k, root)),
        }
    return out
