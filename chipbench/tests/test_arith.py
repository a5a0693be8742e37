"""FLOPs and bytes from the configurations' shapes, against hand counts,
and the model families' arithmetic of every configuration file."""

import pytest

from chipbench import arith, families, harness
from chipbench.families import dense

# per layer: wq + wk + wv + wo + MLP; then the head (d x V)
SC2 = 30 * (3072 * 3072 + 2 * 3072 * 256 + 3072 * 3072 + 2 * 3072 * 12288) + 3072 * 49152
DS = 15 * (4 * 4096 * 4096 + 3 * 4096 * 11008) + 4096 * 102400

HAND = {
    # matmul params, all params, attention FLOPs per query-key pair, KV bytes per token
    "starcoder2_3b": (3_029_336_064, 3_180_518_400, 4 * 24 * 128 * 30, 2 * 30 * 2 * 128 * 2),
    "deepseek_7b_15l": (3_455_057_920, 3_874_615_296, 4 * 32 * 128 * 15, 2 * 15 * 32 * 128 * 2),
}


@pytest.fixture(params=sorted(HAND))
def case(request):
    return request.param, harness.load_config(request.param)["model"], HAND[request.param]


def test_parameter_counts(case):
    name, m, (mm, total, _, _) = case
    assert arith.matmul_params(m) == mm == {"starcoder2_3b": SC2, "deepseek_7b_15l": DS}[name]
    assert arith.param_count(m) == total
    # starcoder2_3b's bf16 weights measured on the chip: 6,361,036,800 bytes
    if name == "starcoder2_3b":
        assert 2 * total == 6_361_036_800


def test_flops_per_token(case):
    _, m, (mm, _, pair, _) = case
    assert arith.decode_flops(m, 1) == 2 * mm + pair
    assert arith.decode_flops(m, 3000) == 2 * mm + 3000 * pair
    assert arith.prefill_flops(m, 1024) == 2 * mm * 1024 + pair * 1024 * 1025 / 2


def test_bytes_per_decode_step(case):
    name, m, (_, total, _, kv) = case
    assert arith.kv_bytes_per_token(m) == kv == {"starcoder2_3b": 30_720,
                                                 "deepseek_7b_15l": 245_760}[name]
    embed = m["vocab_size"] * m["d_model"]
    weights = 2 * (total - embed + m["d_model"])
    assert arith.decode_bytes(m, 0) == weights
    assert arith.decode_bytes(m, 4096) == weights + 4096 * kv


@pytest.mark.parametrize("name", harness.config_names())
def test_prefill_flops_grow_with_the_prompt(name):
    m = harness.load_config(name)["model"]
    flops = [families.of(m).prefill_flops(m, L) for L in (1, 512, 2048, 4096)]
    assert 0 < flops[0] and flops == sorted(set(flops))


@pytest.mark.parametrize("name", [n for n in harness.config_names()
                                  if harness.load_config(n)["model"]["family"] == "dense"])
def test_the_dense_family_counts_as_arith_whatever_the_counters(name):
    m = harness.load_config(name)["model"]
    for n, counters in ((0, {}), (2049, {"position": 7}), (4095, {})):
        assert dense.decode_flops(m, n, counters) == arith.decode_flops(m, n)
        assert dense.decode_bytes(m, n, counters) == arith.decode_bytes(m, n)
        assert dense.prefill_flops(m, n + 1) == arith.prefill_flops(m, n + 1)
    assert dense.tick_counters(engine=None) == {}
