"""The jamba family (``chipbench/families/jamba.py``) and the two readers
that time layer kinds by their named scopes: the reference against the
program at small sizes, the weights bit for bit, the arithmetic from
made-up counters, and the readers on made-up runs of both families."""

from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, weights
from chipbench import traffic as T
from chipbench.families import jamba
from chipbench.metrics import _scoped, ffn_decode_roofline, mixer_prefill_us_per_token
from repro.models import lm

CONF = harness.load_config("jamba2_mini_8l")
FULL = dict(CONF["model"], name="jamba2_mini_8l")
SMALL = dict(CONF["small"], name="small")
DENSE = dict(harness.load_config("deepseek_7b_15l")["model"], name="deepseek_7b_15l")
SEED = 2**33 + 21
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MIX = T.Mix(name="m", prompt_lens=(8,), prompt_weights=(1,), output_mean=2, output_cap=4)


def _flat(tree) -> dict:
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_reference_is_the_programs_model_at_small_sizes():
    """In float32 the program's one pass over a row (dropless experts, the
    chunked scan) and the reference (dense sum over held experts, a plain
    recurrence) give the same logits at every position, to rounding."""
    m = dict(SMALL, dtype="float32")
    cfg = jamba.program_config(m)
    params = weights.served_params(m, SEED)
    row = np.random.default_rng(1).integers(0, m["vocab_size"], reference.PAD).astype(np.int32)
    x, _ = lm._run_stack(params["blocks"], lm._embed(params, jnp.asarray(row)[None], cfg), cfg,
                         mode="prefill")
    program = lm._head(params, x, cfg)[0]
    top = weights.top_f32(m, SEED)
    xs = jamba.hidden(m, SEED, [row], top, False)[0]
    ref = reference.mm(reference.rms(xs, top["final_norm"], m["norm_eps"]), top["embed/unembed"],
                       False)
    np.testing.assert_allclose(np.asarray(program), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_each_positions_weights_are_the_served_ones_bit_for_bit():
    served = _flat(weights.served_params(SMALL, SEED))
    key, mf = weights.seed_key(SEED), weights._frozen(SMALL)
    for s in range(SMALL["num_superblocks"]):
        for i in range(len(SMALL["layers"])):
            for name, leaf in jamba.position_weights(mf, key, jnp.uint32(s), i).items():
                want = served[f"blocks/{i}/{name}"][s]
                if name in jamba.EXPERT_LEAVES:
                    assert leaf.dtype == want.dtype
                np.testing.assert_array_equal(np.asarray(want, np.float32),
                                              np.asarray(leaf, np.float32))


# Jamba2-Mini's share, by hand: one Mamba layer, the attention layer, one
# dense MLP, one expert layer outside its experts, one expert.
D, DI, N, R, F, V = 4096, 8192, 16, 256, 14336, 16384
MAMBA = D * 2 * DI + 4 * DI + DI + DI * (R + 2 * N) + R * DI + DI + DI * N + DI + DI * D + R + 2 * N
MAMBA_MATMUL = D * 2 * DI + DI * (R + 2 * N) + R * DI + DI * D
ATTN = 2 * D * D + 2 * D * 1024
MLP = 3 * D * F
ROUTER = D * 16
EXPERT = 3 * D * F
NORMS = 16 * D + D


def test_parameter_count_of_the_share():
    total = weights.layout(FULL)
    count = sum(int(np.prod(s)) for s, _, _ in total.values())
    assert count == 7 * MAMBA + ATTN + 4 * MLP + 4 * (ROUTER + 8 * EXPERT) + 2 * V * D + NORMS
    assert 2 * count == 14_510_878_656  # bf16 bytes: 14.51 GB


@pytest.mark.parametrize("routed, hit, pairs", [
    ([[1, 0, 0, 0, 0, 0, 0, 1], [0] * 8, [0, 0, 0, 1, 0, 0, 0, 0], [0] * 8], 3, 3),
    ([[0] * 8] * 4, 0, 0),  # every pair went to the other chip's experts
    ([[2, 0, 0, 0, 0, 0, 0, 0]] + [[0, 1, 1, 0, 0, 0, 0, 0]] * 3, 7, 8),  # two slots
])
def test_decode_arithmetic_counts_the_experts_a_step_hit(routed, hit, pairs):
    counters = {"expert_tokens": np.asarray(routed, np.int32)}
    kv = 3000
    other = 7 * MAMBA + ATTN + 4 * MLP + 4 * ROUTER + V * D + NORMS + D  # one embedding row
    state = 7 * 2 * (4 * DI * N + 2 * 3 * DI)
    assert jamba.decode_bytes(FULL, kv, counters) == (
        2 * (other + hit * EXPERT + kv * 2 * 8 * 128) + state)
    matmul = 7 * MAMBA_MATMUL + ATTN + 4 * MLP + 4 * ROUTER + D * V
    scan = 7 * (2 * 4 * DI + 7 * DI * N)
    assert jamba.decode_flops(FULL, kv, counters) == (
        2 * matmul + scan + 2 * pairs * EXPERT + 4 * 32 * 128 * kv)


def test_prefill_counts_one_held_expert_per_token_and_layer():
    matmul = 7 * MAMBA_MATMUL + ATTN + 4 * MLP + 4 * ROUTER + D * V
    per_token = 2 * matmul + 7 * (2 * 4 * DI + 7 * DI * N) + 4 * 2 * EXPERT  # k * held / E = 1
    L = 2048
    assert jamba.prefill_flops(FULL, L) == pytest.approx(
        L * per_token + 4 * 32 * 128 * L * (L + 1) / 2)


def test_tick_counters_hand_over_the_engines_device_array():
    got = jamba.tick_counters(NS(expert_tokens=jnp.ones((4, 8), jnp.int32)))
    assert isinstance(got["expert_tokens"], jax.Array)


# -- the readers on made-up runs --------------------------------------------------

DECODE_SCOPES = {"%fusion.1 bf16[1,14336]": "jit(engine_decode)/while/body/closed_call/moe/dot",
                 "%fusion.2 bf16[1,4096]": "jit(engine_decode)/while/body/closed_call/mlp/dot",
                 "%fusion.3 bf16[1,8192]": "jit(engine_decode)/while/body/closed_call/mamba/x",
                 "%copy.4 bf16[1,4096]": "jit(engine_decode)",
                 # copies name no scope: the shape of Jamba's dense `mlp/wo` is an FFN's,
                 # a norm's (4096,) every kind's
                 "%copy-done.8 bf16[1,14336,4096]": "jit(engine_decode)",
                 "%copy-start.9 (bf16[1,4096]": "jit(engine_decode)"}
PREFILL_SCOPES = {"%fusion.5 f32[1,8192,16]": "jit(engine_prefill)/while/body/mamba/while",
                  "%fusion.6 bf16[1,2048,8]": "jit(engine_prefill)/while/body/attn/dot;attn/x",
                  "%fusion.7 bf16[1,2048,4096]": "jit(engine_prefill)/while/body/mlp/dot",
                  "%copy-done.10 bf16[1,8192,4096]": "jit(engine_prefill)"}  # `mamba/out_proj`'s


def _trace(decode_runs: int, scoped: bool = True) -> dict:
    blank = lambda scopes: {k: v.split("/")[0] for k, v in scopes.items()}
    return {"modules": {"jit_engine_decode(7)": [decode_runs, 0.03],
                        "jit_engine_prefill(8)": [2, 0.5]},
            "op_seconds": {"jit_engine_decode(7)": {"%fusion.1 bf16[1,14336]": 0.012,
                                                    "%fusion.2 bf16[1,4096]": 0.006,
                                                    "%fusion.3 bf16[1,8192]": 0.01,
                                                    "%copy.4 bf16[1,4096]": 0.001,
                                                    "%copy-done.8 bf16[1,14336,4096]": 0.002,
                                                    "%copy-start.9 (bf16[1,4096]": 0.003},
                           "jit_engine_prefill(8)": {"%fusion.5 f32[1,8192,16]": 0.2,
                                                     "%fusion.6 bf16[1,2048,8]": 0.05,
                                                     "%fusion.7 bf16[1,2048,4096]": 0.2,
                                                     "%copy-done.10 bf16[1,8192,4096]": 0.04}},
            "scopes": {"jit_engine_decode": [DECODE_SCOPES if scoped else blank(DECODE_SCOPES)],
                       "jit_engine_prefill": [PREFILL_SCOPES if scoped else blank(PREFILL_SCOPES)]},
            "busy_s": 1.0, "window_s": 2.0}


def _run(model, counters, trace, peak=PEAK):
    ev = lambda phase, tokens: NS(phase=phase, duration_s=0.01, tokens=tokens)
    ticks = [harness.Tick(0.0, 0.5, [ev("prefill", 2048), ev("decode", 1)], [2049], counters[0]),
             harness.Tick(0.5, 0.6, [ev("decode", 1)], [2050], counters[1]),
             harness.Tick(0.6, 1.2, [ev("prefill", 3072), ev("decode", 1)], [3073], counters[2])]
    return harness.Run("c.m", 1, 10.0, 1.0, model, MIX, peak, [], ticks, 3, trace)


ROUTED = [np.asarray(r, np.int32) for r in (
    [[1, 1, 0, 0, 0, 0, 0, 0], [0] * 8, [0, 0, 0, 0, 0, 0, 0, 1], [0, 0, 1, 0, 0, 0, 0, 0]],
    [[0] * 8, [0] * 8, [0] * 8, [0] * 8],
    [[0, 0, 0, 0, 2, 0, 0, 0], [0] * 8, [0] * 8, [0] * 8])]


def test_ffn_roofline_reads_the_experts_each_step_hit():
    counters = [{"expert_tokens": r} for r in ROUTED]
    run = _run(FULL, counters, _trace(3))
    dense = 4 * MLP + 4 * ROUTER
    least = 0.0
    for hit, pairs in ((4, 4), (0, 0), (1, 2)):
        nbytes = 2 * (dense + hit * EXPERT)
        flops = 2 * dense + 2 * pairs * EXPERT
        least += max(nbytes / PEAK["hbm_bytes_per_s"], flops / PEAK["bf16_flops_per_s"])
    got = ffn_decode_roofline.read(run)
    # the moe and mlp ops, and the copy of a dense MLP's weight
    assert got == pytest.approx(100 * least / 3 * 3 / 0.020)
    assert 0 < got < 100


def test_mixer_reader_takes_the_mixers_ops_of_the_prefills():
    run = _run(FULL, [{"expert_tokens": r} for r in ROUTED], _trace(3))
    # the scan, the attention and the copy of a Mamba weight (0.29 s over two runs)
    # over the mean prompt
    assert mixer_prefill_us_per_token.read(run) == pytest.approx(1e6 * 0.29 / 2 / 2560)


def test_weight_copies_count_for_the_kind_whose_weights_they_move():
    kinds = _scoped.weight_kinds(FULL)
    assert kinds[(14336, 4096)] == {"mlp"} and kinds[(8, 4096, 14336)] == {"moe"}
    assert kinds[(8192, 4096)] == {"mamba"} and kinds[(4096, 1024)] == {"attn"}
    assert kinds[(4096,)] == {"attn", "mamba", "mlp", "moe"}  # the norms
    dense = _scoped.weight_kinds(DENSE)  # one position, paths without its index
    assert dense[(11008, 4096)] == dense[(15, 11008, 4096)] == {"mlp"}
    assert dense[(4096, 4096)] == {"attn"}
    trace = _trace(3)
    ffn = _scoped.seconds_in(trace, "jit_engine_decode", _scoped.FFNS, FULL)
    assert ffn == pytest.approx(0.012 + 0.006 + 0.002)
    mixers = _scoped.seconds_in(trace, "jit_engine_decode", _scoped.MIXERS, FULL)
    assert mixers == pytest.approx(0.01)  # the norm-shaped copy counts for no kind


def test_both_readers_read_a_dense_run():
    run = _run(DENSE, [{}] * 3, _trace(3))
    nbytes = 2 * 15 * 3 * 4096 * 11008
    least = max(nbytes / PEAK["hbm_bytes_per_s"], nbytes / PEAK["bf16_flops_per_s"])
    assert ffn_decode_roofline.read(run) == pytest.approx(100 * 3 * least / 0.018)  # 3 steps
    assert mixer_prefill_us_per_token.read(run) == pytest.approx(1e6 * 0.25 / 2 / 2560)


@pytest.mark.parametrize("trace, peak", [
    (_trace(3, scoped=False), PEAK),  # a program that opens no layer scope
    ({}, PEAK),  # no trace
    (_trace(3), None),  # no peak for this device (the roofline only)
])
def test_readers_stay_silent_without_their_inputs(trace, peak):
    run = _run(DENSE, [{}] * 3, trace, peak)
    assert ffn_decode_roofline.read(run) is None
    if peak is not None:
        assert mixer_prefill_us_per_token.read(run) is None


def test_mixer_reader_refuses_runs_that_do_not_match_the_prefills():
    trace = _trace(3)
    trace["modules"]["jit_engine_prefill(8)"] = [5, 0.5]
    assert mixer_prefill_us_per_token.read(_run(DENSE, [{}] * 3, trace)) is None
