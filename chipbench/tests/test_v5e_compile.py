"""Compile each configuration file's timed programs and its reference for a
described v5e chip (no chip needed): the seeded weights, prefill at the
longest prompt of the traffic the file names for its rehearsal, decode at
``max_seq=4096``, and the reference's first superblock and head at 4096
positions. Each must fit one chip's memory.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import families, harness, reference, weights
from chipbench import traffic as T

HBM = 16e9


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


def _bytes(compiled) -> float:
    ma = compiled.memory_analysis()
    return ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes


@pytest.mark.parametrize("name", harness.config_names())
def test_timed_programs_and_reference_compile_for_v5e(name, one_chip):
    from repro.models import lm
    from repro.models.params import init_params

    conf = harness.load_config(name)
    m = dict(conf["model"], name=name)
    mix = harness.load_mix(conf["rehearsal_traffic"])
    family = families.of(m)
    cfg = family.program_config(m)
    sds = lambda x, dt=None: jax.ShapeDtypeStruct(x.shape, dt or x.dtype, sharding=one_chip)

    key = weights.seed_key(1)
    served = weights.served_jit(m).lower(sds(key)).compile()
    params = jax.tree.map(sds, lm.abstract_model(cfg))
    caches = jax.tree.map(sds, jax.eval_shape(lambda: init_params(
        lm.cache_template(cfg, T.SLOTS, T.MAX_SEQ), jax.random.PRNGKey(0), jnp.bfloat16)))
    scalar = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    decode = jax.jit(lambda p, t, pos, c: lm.decode_step(p, cfg, t, pos, c)).lower(
        params, scalar((T.SLOTS, 1)), scalar(()), caches).compile()
    prefill = jax.jit(lambda p, t: lm.prefill(p, cfg, t)).lower(
        params, scalar((1, max(mix.prompt_lens)))).compile()
    weight_bytes = served.memory_analysis().output_size_in_bytes
    # the chip pads the small norm leaves to its tiles: a few kB over the count
    assert weight_bytes == pytest.approx(2 * sum(
        int(jnp.prod(jnp.array(s))) * (m["num_superblocks"] if st else 1)
        for s, st, _ in weights.layout(m).values()), rel=1e-5)
    # weights, the cache and the undonated decode copy of it fit one chip
    assert _bytes(decode) < HBM
    assert _bytes(prefill) + _bytes(decode) - weight_bytes < HBM

    # the reference's first superblock over one row, its weights made inside
    top = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
           for k, (s, st, _) in weights.layout(m).items() if not st}
    one = dict(m, num_superblocks=1)
    layer = jax.jit(lambda top, row: family.hidden(one, 1, [row], top, False)[0]).lower(
        top, scalar((T.MAX_SEQ,))).compile()
    x = jax.ShapeDtypeStruct((T.MAX_SEQ, m["d_model"]), jnp.float32, sharding=one_chip)
    head = reference._head.lower(x, top, scalar((T.MAX_SEQ, 2)), m["norm_eps"], False).compile()
    assert _bytes(layer) + _bytes(head) < HBM / 2
