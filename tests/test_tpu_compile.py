"""Compile rehearsals for a v5e chip that is described, not attached.

The TPU compiler is installed wherever libtpu is, so these tests compile the
accelerator paths for one chip of a described ``v5e:2x2`` topology and
refuse what the chip's compiler would refuse: misaligned Pallas blocks,
programs that do not fit the device, dtypes the chip lacks. Nothing runs,
so nothing here says anything about results or times.

The topology is described only inside the module fixture, never while the
module is imported: one process at a time may load the TPU library, and
every test worker imports every test file.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """ShapeDtypeStructs of ``tree`` placed on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding), tree)


def _kernel_cases():
    """The six Pallas kernels at their ``benchmarks/kernel_bench.py`` shapes
    and block sizes: (jitted call, argument shapes)."""
    from repro.kernels.decision_scan.ops import decision_scan
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.lindley_scan.ops import lindley_scan
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.ssm_scan.ops import ssm_scan

    f32 = partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    return {
        "flash_attention": (partial(flash_attention, blk_q=64, blk_k=64),
                            [f32((1, 256, 4, 64)), f32((1, 256, 2, 64)),
                             f32((1, 256, 2, 64))]),
        "decode_attention": (partial(decode_attention, blk_k=128),
                             [f32((2, 1, 8, 64)), f32((2, 512, 2, 64)),
                              f32((2, 512, 2, 64)),
                              jax.ShapeDtypeStruct((), jnp.int32)]),
        "ssm_scan": (partial(ssm_scan, blk_t=32, blk_d=128),
                     [f32((2, 128, 128)), f32((2, 128, 8)), f32((2, 128, 8)),
                      f32((2, 128, 128)), f32((128, 8))]),
        "rmsnorm": (rmsnorm, [f32((8, 128, 512)), f32((512,))]),
        "lindley_scan": (partial(lindley_scan, blk_b=16, blk_t=256),
                         [f32((16, 1024)), f32((16, 1024))]),
        "decision_scan": (partial(decision_scan, hysteresis=0.15, stagger=4,
                                  blk_n=16, blk_t=64),
                          [f32((256, 16, 5)), jax.ShapeDtypeStruct((16,), jnp.int32)]),
    }


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention", "ssm_scan",
                                  "rmsnorm", "lindley_scan", "decision_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    compiled = jax.jit(partial(fn, impl="pallas")).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def starcoder(one_chip):
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("starcoder2_3b")
    return cfg, _on(one_chip, lm.abstract_model(cfg))


def test_starcoder2_decode_step_fits_one_chip(one_chip, starcoder):
    from repro.models import lm
    from repro.models.params import abstract_params

    cfg, params = starcoder
    assert (cfg.d_model, cfg.num_superblocks, cfg.dtype) == (3072, 30, "bfloat16")
    caches = _on(one_chip, abstract_params(lm.cache_template(cfg, 4, 512),
                                           jnp.dtype(cfg.dtype)))
    tok = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t, i, c: lm.decode_step(p, cfg, t, i, c)) \
        .lower(params, tok, pos, caches).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < V5E_HBM_BYTES


def test_starcoder2_prefill_fits_one_chip(one_chip, starcoder):
    from repro.models import lm

    cfg, params = starcoder
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t: lm.prefill(p, cfg, t)).lower(params, tokens).compile()
    assert compiled.memory_analysis().argument_size_in_bytes < V5E_HBM_BYTES


def test_fleet_tail_euler_compiles_without_complex(one_chip):
    """The exact tail inversion carries its contour as real float64 pairs;
    the chip's compiler refuses complex128."""
    from repro.core.latency import NetworkPath, ServiceModel, Tier, Workload
    from repro.core.scenario import EdgeSpec, Scenario
    from repro.core.tail import euler_grow_iters
    from repro.fleet import ScenarioBatch
    from repro.fleet.tail_vec import _fleet_tail_jit, _uniform_kind_hint
    from repro.jaxenv import x64

    det = ServiceModel.DETERMINISTIC
    scn = [Scenario(workload=Workload(rate, 30_000, 1_000),
                    device=Tier("dev", 0.15, service_model=det),
                    edges=(EdgeSpec(Tier("edge", 0.028, service_model=det)),),
                    network=NetworkPath(5e6 / 8))
           for rate in (0.5, 2.0, 5.0)]
    cols = ScenarioBatch.from_scenarios(scn).arrays()
    with x64():
        shapes = _on(one_chip, {k: jnp.asarray(v) for k, v in cols.items()})
        q = jax.ShapeDtypeStruct((), jnp.float64, sharding=one_chip)
        lowered = _fleet_tail_jit.lower(
            shapes, q, method="euler", grow_iters=euler_grow_iters(0.99),
            dev_hint=_uniform_kind_hint(cols["dev_model"]),
            proc_hint=_uniform_kind_hint(cols["edge_model"]))
        assert "complex" not in lowered.as_text()
        lowered.compile()


LAYER_CACHE = re.compile(r"^(?:ROOT )?%\S+ = bf16\[(?:1,)?1,4096,32,128\]")


def _unfused_instructions(hlo: str) -> list[str]:
    """Instructions of a compiled module's text that lie outside the
    computations its fusions call."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name and line.strip() not in ("", "}"):
            comps[name].append(line.strip())
    fused = {c for lines in comps.values() for l in lines if " fusion(" in l
             for c in re.findall(r"calls=%([\w.\-]+)", l)}
    assert fused, "no fusion found: the module text is not what this parser reads"
    return [l for c, lines in comps.items() if c not in fused for l in lines]


def test_engine_decode_writes_its_cache_in_place(one_chip):
    """The engine's decode at deepseek_7b_15l's widths (15 of 30 layers, one
    slot of 4096 positions): the donated caches alias the output whole, the
    program needs less scratch than one layer's K+V, and no layer-sized
    copy of the cache exists outside a fusion (the attention einsums read
    the stacked buffer)."""
    from dataclasses import replace

    from repro.configs import get_config
    from repro.models import lm
    from repro.models.params import abstract_params
    from repro.serving.engine import jit_programs

    cfg = replace(get_config("deepseek_7b"), num_superblocks=15)
    params = _on(one_chip, lm.abstract_model(cfg))
    caches = _on(one_chip, abstract_params(lm.cache_template(cfg, 1, 4096),
                                           jnp.dtype(cfg.dtype)))
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jit_programs(cfg)[0].lower(params, tok, pos, caches).compile()
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    layer_kv = 2 * 4096 * cfg.kv_dim * 2
    assert cache_bytes == 15 * layer_kv == 1_006_632_960
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < layer_kv
    assert not [l for l in _unfused_instructions(compiled.as_text()) if LAYER_CACHE.match(l)]
