"""FLOPs and bytes from the configurations' shapes, against hand counts."""

import pytest

from chipbench import arith, harness

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
