"""Operations and bytes that the algorithm needs, from a configuration's
shapes: the dense family's arithmetic (``chipbench/families/dense.py``).

Copied from the program's FLOP arithmetic (``2N`` per token over the
parameters that enter a matmul, the input-embedding gather excluded and the
head included, plus the attention term) so that the yardstick cannot move
with the program. Attention counts the query-key pairs a causal mask keeps:
``L (L + 1) / 2`` for a prefill of ``L`` tokens and ``n`` for one decoded
token whose cache holds ``n`` valid positions. Bytes count what a decode
step has to read: every weight but the embedding table (one row of it is
gathered), and the keys and values of the valid positions only: not the
unused capacity of the cache, and not a copy of it.
"""

from __future__ import annotations


def _dims(m: dict):
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return m["d_model"], H * hd, K * hd, m["d_ff"], m["vocab_size"], m["num_superblocks"]


def matmul_params(m: dict) -> int:
    d, q, kv, f, V, n = _dims(m)
    mlp = (3 if m["gated_mlp"] else 2) * d * f
    return n * (d * q + 2 * d * kv + q * d + mlp) + d * V


def param_count(m: dict) -> int:
    d, q, kv, f, V, n = _dims(m)
    return matmul_params(m) + V * d + (2 * n + 1) * d


def _dtype_bytes(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["dtype"]]


def _attn_flops_per_pair(m: dict) -> float:
    """QK^T and PV for one query-key pair, summed over heads and layers."""
    return 2.0 * 2.0 * m["num_heads"] * m["head_dim"] * m["num_superblocks"]


def prefill_flops(m: dict, L: int) -> float:
    return 2.0 * matmul_params(m) * L + _attn_flops_per_pair(m) * L * (L + 1) / 2


def decode_flops(m: dict, kv_len: int) -> float:
    return 2.0 * matmul_params(m) + _attn_flops_per_pair(m) * kv_len


def kv_bytes_per_token(m: dict) -> int:
    _, _, kv, _, _, n = _dims(m)
    return 2 * n * kv * _dtype_bytes(m)


def decode_bytes(m: dict, kv_len: int) -> float:
    """Weights read (all but the embedding table, plus one gathered row) and
    the valid keys and values."""
    d = m["d_model"]
    weights = (param_count(m) - m["vocab_size"] * d + d) * _dtype_bytes(m)
    return float(weights + kv_len * kv_bytes_per_token(m))
