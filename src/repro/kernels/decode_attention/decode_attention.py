"""GQA decode attention for TPU: split-KV flash-decode.

One new token attends to a long cache (32k-500k). The cache is swept in
``blk_k`` tiles (grid dim innermost, "arbitrary"); the G grouped query heads
of one kv head ride together as the tile's row dim, so the MXU sees
(G x hd) @ (hd x blk_k) — exactly the FlashDecoding split-KV shape
[arXiv:2311.01282], with the cross-device split handled by sequence-sharded
caches (DESIGN.md §6) and the within-device sweep by this kernel. The valid
length ``pos`` arrives via scalar prefetch (it is a traced runtime value).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_kernel", "decode_attention_pallas"]

NEG_INF = -2.0e38


def _compiler_params(grid_len: int):
    sem = ("parallel",) * (grid_len - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


def decode_attention_kernel(
    pos_ref,  # scalar prefetch: (1,) int32
    q_ref,  # (1, 1, G, hd)
    k_ref,  # (1, 1, blk_k, hd)
    v_ref,
    o_ref,  # (1, 1, G, hd)
    acc_ref,  # (G, hd) f32
    m_ref,  # (G,) f32
    l_ref,
    *,
    scale: float,
    softcap: float,
    blk_k: int,
    n_k_blocks: int,
):
    ik = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ik * blk_k
    live = k_start <= pos  # tile entirely past the valid region -> skip

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (blk_k, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, blk_k)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,  # (B, H, hd)
    k: jax.Array,  # (B, K, S, hd)
    v: jax.Array,
    pos: jax.Array,  # scalar int32
    *,
    softcap: float = 0.0,
    scale: float | None = None,
    blk_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    K, S = k.shape[1], k.shape[2]
    G = H // K
    scale = hd**-0.5 if scale is None else scale
    blk_k = min(blk_k, S)
    assert S % blk_k == 0
    nk = S // blk_k
    qr = q.reshape(B, K, G, hd)

    kernel = functools.partial(
        decode_attention_kernel,
        scale=scale,
        softcap=softcap,
        blk_k=blk_k,
        n_k_blocks=nk,
    )
    grid = (B, K, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, kh, ik, pos_ref: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, kh, ik, pos_ref: (b, kh, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, kh, ik, pos_ref: (b, kh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, kh, ik, pos_ref: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), qr, k, v)
    return out.reshape(B, H, hd)
