"""Flash attention for TPU: VMEM-tiled online-softmax (FlashAttention
[arXiv:2205.14135] reimagined for the TPU memory hierarchy per DESIGN.md §5).

Layout is head-major (B, H, S, hd) so each grid cell streams contiguous
(blk, hd) tiles HBM->VMEM. Grid = (batch, q-head, q-block, kv-block) with the
kv-block dim innermost and sequence-ordered ("arbitrary" semantics): the fp32
accumulator, running max m, and running sum l live in VMEM scratch across the
kv sweep, exactly the role SRAM plays in the CUDA original. GQA is folded
into the k/v index_map (q head h reads kv head h // G). Causal and
sliding-window masks are applied both block-wise (pl.when skips dead tiles'
compute) and element-wise; gemma2 soft-capping runs on the fp32 scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

NEG_INF = -2.0e38


def _compiler_params(grid_len: int):
    sem = ("parallel",) * (grid_len - 1) + ("arbitrary",)
    return pltpu.CompilerParams(dimension_semantics=sem)


def flash_attention_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    blk_q: int,
    blk_k: int,
    n_k_blocks: int,
    q_offset: int,
):
    """One (b, h, iq, ik) grid cell. Refs are (blk_q, hd) / (blk_k, hd)."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this tile
    q_start = iq * blk_q + q_offset
    k_start = ik * blk_k
    # block-level liveness: causal kills tiles fully above the diagonal,
    # window kills tiles fully left of the band
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + blk_q - 1
    if window > 0:
        live &= (k_start + blk_k - 1) > (q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (blk_q, blk_k)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, H, Sq, hd)
    k: jax.Array,  # (B, K, Skv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    Bk, K, Skv, _ = k.shape
    G = H // K
    scale = hd**-0.5 if scale is None else scale
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)
    assert Sq % blk_q == 0 and Skv % blk_k == 0, (Sq, blk_q, Skv, blk_k)
    nq, nk = Sq // blk_q, Skv // blk_k
    q_offset = Skv - Sq  # queries are the tail of the kv sequence

    kernel = functools.partial(
        flash_attention_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        blk_q=blk_q,
        blk_k=blk_k,
        n_k_blocks=nk,
        q_offset=q_offset,
    )
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, hd), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
        ],
        compiler_params=_compiler_params(len(grid)),
        interpret=interpret,
    )(q, k, v)
