"""The dense family: every superblock is one layer, pre-norm RMSNorm with
``(1 + scale)``, rotate-half RoPE attention (MHA or GQA), and a plain
GELU(tanh) or SwiGLU MLP; the head is untied.

The reference follows these equations, which are the program's, departures
from the published models included; the configuration files list those
departures. The arithmetic is ``chipbench/arith.py``'s, which ignores the
tick counters: a dense step reads every weight whatever its tokens.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench import arith, weights
from chipbench.reference import attention, mm, rms

PROGRAM_KEYS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff", "vocab_size",
                "num_superblocks", "gated_mlp", "mlp_act", "rope_theta", "norm_eps", "dtype")
REFERENCE_KEYS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "gated_mlp",
                  "rope_theta", "norm_eps")


def program_config(m: dict):
    from repro.configs.base import LayerSpec, ModelConfig

    return ModelConfig(name=m["name"], family="dense",
                       superblock=tuple(LayerSpec(*k) for k in m["layers"]),
                       **{k: m[k] for k in PROGRAM_KEYS})


def layout(m: dict) -> dict[str, tuple[tuple[int, ...], bool, float]]:
    d, H, K, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    f, V = m["d_ff"], m["vocab_size"]
    q, kv = H * hd, K * hd
    out = {
        "embed/embedding": ((V, d), False, weights.EMBED_STD),
        "embed/unembed": ((d, V), False, d ** -0.5),
        "final_norm": ((d,), False, weights.NORM_STD),
        "blocks/norm1": ((d,), True, weights.NORM_STD),
        "blocks/attn/wq": ((d, q), True, d ** -0.5),
        "blocks/attn/wk": ((d, kv), True, d ** -0.5),
        "blocks/attn/wv": ((d, kv), True, d ** -0.5),
        "blocks/attn/wo": ((q, d), True, q ** -0.5),
        "blocks/norm2": ((d,), True, weights.NORM_STD),
        "blocks/mlp/wi": ((d, f), True, d ** -0.5),
        "blocks/mlp/wo": ((f, d), True, f ** -0.5),
    }
    if m["gated_mlp"]:
        out["blocks/mlp/wg"] = ((d, f), True, d ** -0.5)
    return out


def rope(x, theta):
    """x: (T, H, hd), positions 0..T-1, rotate-half convention."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnums=(2, 3))
def layer(xs, w, mf, fp8):
    """xs: (T, d) float32 -> the same after one decoder layer; ``mf`` holds
    :data:`REFERENCE_KEYS` as ``(key, value)`` pairs."""
    m = dict(mf)
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    T = xs.shape[0]
    h = rms(xs, w["norm1"], m["norm_eps"])
    q = rope(mm(h, w["attn/wq"], fp8).reshape(T, H, hd), m["rope_theta"])
    k = rope(mm(h, w["attn/wk"], fp8).reshape(T, K, hd), m["rope_theta"])
    v = mm(h, w["attn/wv"], fp8).reshape(T, K, hd)
    xs = xs + mm(attention(q, k, v).reshape(T, H * hd), w["attn/wo"], fp8)
    h = rms(xs, w["norm2"], m["norm_eps"])
    if m["gated_mlp"]:
        a = jax.nn.silu(mm(h, w["mlp/wg"], fp8)) * mm(h, w["mlp/wi"], fp8)
    else:
        a = jax.nn.gelu(mm(h, w["mlp/wi"], fp8), approximate=True)
    return xs + mm(a, w["mlp/wo"], fp8)


def static(m: dict) -> tuple:
    return tuple((k, m[k]) for k in REFERENCE_KEYS)


def hidden(m: dict, seed: int, rows: list, top: dict, fp8: bool) -> list:
    """Final hidden states of token rows, a layer at a time: each layer's
    weights are made once and applied to every row."""
    xs = [jnp.take(top["embed/embedding"], jnp.asarray(t), axis=0) for t in rows]
    for i in range(m["num_superblocks"]):
        w = weights.layer_f32(m, seed, i)
        xs = [layer(x, w, static(m), fp8) for x in xs]
    return xs


def prefill_flops(m: dict, L: int) -> float:
    return arith.prefill_flops(m, L)


def decode_flops(m: dict, kv_len: int, counters: dict) -> float:
    return arith.decode_flops(m, kv_len)


def decode_bytes(m: dict, kv_len: int, counters: dict) -> float:
    return arith.decode_bytes(m, kv_len)


def tick_counters(engine) -> dict:
    return {}
