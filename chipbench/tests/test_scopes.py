"""The scope map (``chipbench/scopes.py``): HLO instructions to the
``jax.named_scope`` path they were traced under, and the device time a
reader sums under a scope."""

import os
from pathlib import Path

import jax
import pytest

from chipbench import harness, scopes, xplane
from chipbench import traffic as T
from chipbench.tests.record_engine_trace import MODEL, SEED

DATA = Path(__file__).resolve().parent / "data" / "engine.xplane.pb"
MIX = T.Mix(name="small", prompt_lens=(8, 24), prompt_weights=(1, 1), output_mean=4,
            output_cap=8)

HLO = """HloModule jit_engine_decode, is_scheduled=true, entry_computation_layout={()}

%fused_computation.1 (param_0: bf16[64]) -> bf16[64] {
  %param_0 = bf16[64]{0} parameter(0)
  ROOT %negate.1 = bf16[64]{0} negate(%param_0), metadata={op_name="jit(engine_decode)/neg"}
}

ENTRY %main (p0: bf16[64]) -> (bf16[64], s32[]) {
  %p0 = bf16[64]{0:T(256)} parameter(0)
  %copy-start = (bf16[64]{0:T(256)}, bf16[64]{0:T(256)}, u32[]{:S(2)}) copy-start(%p0)
  %fusion.3 = bf16[64]{0:T(256)} fusion(%p0), kind=kLoop, calls=%fused_computation.1, metadata={op_type="neg" op_name="jit(engine_decode)/block/mlp/neg" source_file="lm.py"}
  ROOT %tuple = (bf16[64]{0}, s32[]) tuple(%fusion.3, %c), metadata={op_name="jit(engine_decode)/tuple"}
}
"""


def test_instructions_map_to_their_scope():
    program, found = scopes.hlo_scopes(HLO)
    assert program == "jit_engine_decode"
    assert found["%fusion.3 bf16[64]"] == "jit(engine_decode)/block/mlp/neg"
    assert found["%tuple (bf16[64]"] == "jit(engine_decode)/tuple"
    assert found["%negate.1 bf16[64]"] == "jit(engine_decode)/neg"
    # no metadata: the program's outermost scope
    assert found["%copy-start (bf16[64]"] == "jit(engine_decode)"
    assert found["%p0 bf16[64]"] == "jit(engine_decode)"


def test_seconds_under_a_scope_take_the_map_of_the_program_that_ran():
    long = {"%fusion.1 bf16[1,24]": "jit(engine_prefill)/attn/dot",
            "%fusion.2 bf16[64]": "jit(engine_prefill)/mlp/dot"}
    short = {"%fusion.1 bf16[1,8]": "jit(engine_prefill)/mlp/dot",
             "%fusion.2 bf16[64]": "jit(engine_prefill)/attn/dot"}
    trace = {"op_seconds": {"jit_engine_prefill(1)": {"%fusion.1 bf16[1,24]": 3.0,
                                                      "%fusion.2 bf16[64]": 1.0},
                            "jit_engine_prefill(2)": {"%fusion.1 bf16[1,8]": 0.5,
                                                      "%fusion.2 bf16[64]": 0.25}},
             "modules": {"jit_engine_prefill(1)": [2, 4.0], "jit_engine_prefill(2)": [1, 0.8]},
             "scopes": {"jit_engine_prefill": [short, long]}}
    assert scopes.seconds_under(trace, "jit_engine_prefill", "jit(engine_prefill)/attn") == 3.25
    assert scopes.seconds_under(trace, "jit_engine_prefill", "jit(engine_prefill)/mlp") == 1.5
    assert scopes.seconds_under(trace, "jit_engine_decode", "jit(engine_decode)") is None
    assert scopes.seconds_under({}, "jit_engine_prefill", "") is None
    cover = scopes.coverage(trace)
    assert cover["jit_engine_prefill(2)"] == {"runs": 1, "module_s": 0.8, "ops_s": 0.75,
                                              "mapped_s": 0.75, "scoped_s": 0.75}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def test_every_recorded_decode_op_finds_its_scope(one_chip):
    """The engine's own decode program at the recorded trace's sizes,
    compiled for a described v5e: each op that ran inside
    ``jit_engine_decode`` on the chip is an instruction of it."""
    engine = harness.build_engine(dict(MODEL), SEED)
    fn, args = scopes.engine_programs(engine, MIX)[0]
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                          args)
    program, found = scopes.hlo_scopes(fn.lower(*shapes).compile().as_text())
    assert program == "jit_engine_decode"

    devices, modules, host = xplane.read_events(DATA)
    out = xplane.reduce(devices, host, min(s for _, s, _ in host), max(e for _, _, e in host),
                        programs=modules)
    runs = [r for r in out["op_seconds"] if r.startswith("jit_engine_decode(")]
    assert len(runs) == 1 and out["modules"][runs[0]][0] >= 5
    ops = out["op_seconds"][runs[0]]
    assert len(ops) > 20
    for op in ops:
        assert found[op].startswith("jit(engine_decode)"), op
    # the ops' self time is the program's device time, less gaps inside it
    secs = out["modules"][runs[0]][1]
    assert 0.9 * secs <= sum(ops.values()) <= secs * (1 + 1e-9)
