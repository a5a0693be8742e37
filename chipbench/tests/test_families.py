"""A model family brought by new files alone.

This module is the whole of a toy family: a superblock of two positions,
each a NoPE attention layer and a SwiGLU MLP with weight paths of its own
(``blocks/0/...``, ``blocks/1/...``), a float32 reference, arithmetic that
reads a tick counter, and one counter value per decode step. The tests
register it as ``chipbench.families.toy`` and stand in for its
configuration file, and then drive it through ``harness.run_cell`` on the
CPU: no file of the harness, the weights, the reference, the arithmetic or
the readers knows of it. The dense family's numbers are held elsewhere
(``test_arith.py``, ``test_weights.py``, ``test_harness_cpu.py``).
"""

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, harness, reference, weights
from chipbench import traffic as T
from chipbench.metrics import decode_hbm_roofline, window_mfu
from chipbench.metrics._common import service
from chipbench.reference import mm
from chipbench.tests.test_harness_cpu import FAULTS, SMALL_LIMIT, SMALL_MIX

SEED = 2**34 + 9
POSITIONS = 2
MODEL = dict(family="toy", d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
             vocab_size=512, num_superblocks=2, norm_eps=1e-6, dtype="bfloat16")
CONF = {"model": MODEL, "check": {"max_logit_gap": SMALL_LIMIT}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# -- the family ---------------------------------------------------------------


def program_config(m: dict):
    from repro.configs.base import LayerSpec, ModelConfig

    return ModelConfig(name=m["name"], family="toy", d_model=m["d_model"],
                       num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
                       head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
                       superblock=(LayerSpec("attn", "mlp"),) * POSITIONS,
                       num_superblocks=m["num_superblocks"], rope=False, gated_mlp=True,
                       mlp_act="silu", norm_eps=m["norm_eps"], dtype=m["dtype"])


def layout(m: dict) -> dict:
    d, q, kv, f, V = (m["d_model"], m["num_heads"] * m["head_dim"],
                      m["num_kv_heads"] * m["head_dim"], m["d_ff"], m["vocab_size"])
    out = {"embed/embedding": ((V, d), False, weights.EMBED_STD),
           "embed/unembed": ((d, V), False, d ** -0.5),
           "final_norm": ((d,), False, weights.NORM_STD)}
    for i in range(POSITIONS):
        out |= {f"blocks/{i}/norm1": ((d,), True, weights.NORM_STD),
                f"blocks/{i}/attn/wq": ((d, q), True, d ** -0.5),
                f"blocks/{i}/attn/wk": ((d, kv), True, d ** -0.5),
                f"blocks/{i}/attn/wv": ((d, kv), True, d ** -0.5),
                f"blocks/{i}/attn/wo": ((q, d), True, q ** -0.5),
                f"blocks/{i}/norm2": ((d,), True, weights.NORM_STD),
                f"blocks/{i}/mlp/wi": ((d, f), True, d ** -0.5),
                f"blocks/{i}/mlp/wg": ((d, f), True, d ** -0.5),
                f"blocks/{i}/mlp/wo": ((f, d), True, f ** -0.5)}
    return out


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(xs, w, heads, kv_heads, eps, fp8):
    T_, d = xs.shape
    hd = w["attn/wq"].shape[1] // heads
    h = reference.rms(xs, w["norm1"], eps)
    q = mm(h, w["attn/wq"], fp8).reshape(T_, heads, hd)
    k = mm(h, w["attn/wk"], fp8).reshape(T_, kv_heads, hd)
    v = mm(h, w["attn/wv"], fp8).reshape(T_, kv_heads, hd)
    xs = xs + mm(reference.attention(q, k, v).reshape(T_, heads * hd), w["attn/wo"], fp8)
    h = reference.rms(xs, w["norm2"], eps)
    return xs + mm(jax.nn.silu(mm(h, w["mlp/wg"], fp8)) * mm(h, w["mlp/wi"], fp8),
                   w["mlp/wo"], fp8)


def hidden(m: dict, seed: int, rows: list, top: dict, fp8: bool) -> list:
    xs = [jnp.take(top["embed/embedding"], jnp.asarray(t), axis=0) for t in rows]
    for s in range(m["num_superblocks"]):
        w = weights.layer_f32(m, seed, s)
        for i in range(POSITIONS):
            wi = {k.removeprefix(f"{i}/"): v for k, v in w.items() if k.startswith(f"{i}/")}
            xs = [_layer(x, wi, m["num_heads"], m["num_kv_heads"], m["norm_eps"], fp8)
                  for x in xs]
    return xs


def _weight_elements(m: dict) -> int:
    n = m["num_superblocks"]
    return sum(int(np.prod(s)) * (n if st else 1) for s, st, _ in layout(m).values())


def prefill_flops(m: dict, L: int) -> float:
    return L * decode_flops(m, 0, {})


def decode_flops(m: dict, kv_len: int, counters: dict) -> float:
    return 2.0 * (_weight_elements(m) - m["vocab_size"] * m["d_model"])


def decode_bytes(m: dict, kv_len: int, counters: dict) -> float:
    """Weights, and the cache up to the position the counter gives."""
    kv = 2 * POSITIONS * m["num_superblocks"] * m["num_kv_heads"] * m["head_dim"] * 2
    return 2.0 * _weight_elements(m) + counters["position"] * kv


def tick_counters(engine) -> dict:
    return {"position": int(engine.positions.max())}


# -- the tests ----------------------------------------------------------------


@pytest.fixture
def toy(monkeypatch):
    """This module as ``chipbench.families.toy``, and its configuration."""
    monkeypatch.setitem(sys.modules, "chipbench.families.toy", sys.modules[__name__])
    load = harness.load_config
    monkeypatch.setattr(harness, "load_config",
                        lambda name: CONF if name == "toy" else load(name))
    return dict(MODEL, name="toy")


def _run(tmp_path):
    return harness.run_cell(harness.load_spec(), harness.cell_named("toy.small"), SEED, 1.0,
                            False, time.perf_counter(), peak=None, mix=SMALL_MIX,
                            out_dir=tmp_path)


def test_positions_get_weights_of_their_own(toy):
    assert families.of(toy) is sys.modules[__name__]
    blocks = weights.served_params(toy, SEED)["blocks"]
    assert len(blocks) == POSITIONS and set(blocks[0]) == set(blocks[1])
    for a, b in zip(jax.tree.leaves(blocks[0]), jax.tree.leaves(blocks[1])):
        assert a.shape[0] == toy["num_superblocks"] and not np.array_equal(a, b)
    one = weights.layer_f32(toy, SEED, 1)
    np.testing.assert_array_equal(np.asarray(blocks[1]["attn"]["wq"][1], np.float32),
                                  np.asarray(one["1/attn/wq"]))
    # any layer kinds the program's tree matches are taken
    assert harness.build_engine(toy, SEED).cfg.num_layers == POSITIONS * 2


def test_toy_family_runs_correct_end_to_end(toy, tmp_path):
    res = _run(tmp_path)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["check"]["checked_tokens"]["value"] >= 20


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_toy_timed_path_is_not_correct(fault, toy, monkeypatch, tmp_path):
    owner, attr, broken = FAULTS[fault]
    monkeypatch.setattr(owner, attr, broken)
    assert not _run(tmp_path)["correct"]


def test_counters_are_kept_per_tick_and_read_by_the_arithmetic(toy):
    engine = harness.build_engine(toy, SEED)
    harness.warm_up(engine, SMALL_MIX)
    _, ticks, _, _ = harness.drive(engine, harness.Load(SMALL_MIX, SEED, toy["vocab_size"]), 0.5,
                                   counters=tick_counters)
    decoding = [t for t in ticks if t.decode_kv]
    assert decoding and all(t.counters["position"] == t.decode_kv[0] for t in decoding)
    run = harness.Run("toy.small", SEED, 0.5, 1.0, toy, SMALL_MIX, PEAK, [], ticks, len(ticks),
                      {"modules": {"jit_engine_decode(1)": [len(decoding), 1e-3]}})
    least = sum(decode_bytes(toy, 0, t.counters) / PEAK["hbm_bytes_per_s"] for t in decoding)
    assert decode_hbm_roofline.read(run) == pytest.approx(100 * least / 1e-3)
    tokens = sum(ev.tokens for ev in service(run, "prefill")) + len(decoding)
    assert T.SLOTS == 1  # one decoded token per decoding tick
    assert window_mfu.read(run) == pytest.approx(
        100 * tokens * decode_flops(toy, 0, {}) / (0.5 * PEAK["bf16_flops_per_s"]))
