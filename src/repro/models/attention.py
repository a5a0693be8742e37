"""Attention: GQA with RoPE, optional sliding window + softcap, KV caches.

Three execution paths:
  * full-sequence (train / prefill): query-chunked online attention — the
    XLA analogue of flash attention (bounded score memory at 32k+); the
    Pallas kernel in repro.kernels.flash_attention is the TPU hot path.
  * decode: one query token against a cache. Global layers use an append
    cache; local (sliding-window) layers use a ring buffer of size W whose
    slot->absolute-position mapping is computed analytically (no stored
    position tensor). The step writes its one K/V row into the caches of the
    whole stack in place. Split-KV decode maps to sequence-sharded caches.
  * cross-attention (enc-dec): queries against cached encoder K/V.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.partition import hint

from .layers import rope_apply, softcap
from .params import TSpec

__all__ = [
    "attn_template",
    "kv_cache_template",
    "attn_forward",
    "attn_decode",
    "cross_attn_forward",
    "mha_reference",
]

NEG_INF = -2.0e38  # fp32-safe mask value


def attn_template(cfg: ModelConfig) -> dict:
    d, q, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return {
        "wq": TSpec((d, q), ("embed", "qkv"), init="fan_in"),
        "wk": TSpec((d, kv), ("embed", "kv"), init="fan_in"),
        "wv": TSpec((d, kv), ("embed", "kv"), init="fan_in"),
        "wo": TSpec((q, d), ("qkv", "embed"), init="fan_in"),
    }


def kv_cache_template(cfg: ModelConfig, batch: int, cache_len: int, *, local: bool) -> dict:
    s = min(cache_len, cfg.window_size) if local else cache_len
    shape = (batch, s, cfg.num_kv_heads, cfg.resolved_head_dim)
    axes = ("cache_batch", "cache_seq", None, None)
    return {
        "k": TSpec(shape, axes, init="zeros"),
        "v": TSpec(shape, axes, init="zeros"),
    }


# ---------------------------------------------------------------------------
# Core scaled-dot-product with GQA + masks (single q block)
# ---------------------------------------------------------------------------


def _sdpa_block(q, k, v, *, mask, cap, scale):
    """q: (B, Sq, K, G, hd); k/v: (B, Sk, K, hd); mask: broadcastable to
    (B, K, G, Sq, Sk) bool (True = attend). Returns (B, Sq, K, G, hd)."""
    scores = jnp.einsum(
        "bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * scale
    scores = softcap(scores, cap)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskh->bqkgh", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.astype(v.dtype)


def _mask_block(q_pos, k_pos, *, causal: bool, window: int, k_valid=None):
    """(Sq, Sk) bool mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > q_pos[:, None] - window
    if k_valid is not None:
        m &= k_valid[None, :]
    return m


# ---------------------------------------------------------------------------
# Full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------


def attn_forward(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    local: bool = False,
    return_kv: bool = False,
    positions: jax.Array | None = None,
    external_kv: tuple[jax.Array, jax.Array] | None = None,
):
    """x: (B, S, d). Query-chunked attention over the full sequence.

    ``external_kv`` supplies precomputed (k, v) — the cross-attention path —
    in which case the k/v projections, rope-on-k, and causality are skipped.
    """
    B, S, _ = x.shape
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    H = cfg.num_heads
    G = H // K
    q = hint(x @ p["wq"], "batch", "seq_inner", "qkv").reshape(B, S, K, G, hd)
    if external_kv is None:
        k = hint(x @ p["wk"], "batch", "seq_inner", "kv").reshape(B, S, K, hd)
        v = hint(x @ p["wv"], "batch", "seq_inner", "kv").reshape(B, S, K, hd)
    else:
        k, v = external_kv
        causal = False

    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    key_positions = jnp.arange(k.shape[1], dtype=jnp.int32)
    if cfg.rope and external_kv is None:  # cross-attention carries no rotary
        q = rope_apply(q.reshape(B, S, K * G, hd), positions, cfg.rope_theta).reshape(
            B, S, K, G, hd
        )
        k = rope_apply(k, positions, cfg.rope_theta)

    window = cfg.window_size if local else 0
    scale = hd**-0.5
    chunk = min(cfg.seq_chunk, S)
    # pad the query side to a chunk multiple (keys untouched -> exact);
    # padded rows are sliced off below.
    S_pad = ((S + chunk - 1) // chunk) * chunk
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0), (0, 0)))
    n_chunks = S_pad // chunk

    # Banded keys for sliding-window layers (§Perf iteration "local-band"):
    # a q-chunk at offset o only attends keys in (o - W, o + chunk), so slice
    # that band instead of scoring all S keys and masking — at 32k prefill
    # this cuts the local layers' attention FLOPs/bytes by ~7x.
    band = window + chunk if window > 0 else 0
    use_band = 0 < band < k.shape[1] and external_kv is None

    def one_chunk(qc, offset):
        q_pos = offset + jnp.arange(chunk, dtype=jnp.int32)
        if use_band:
            start = jnp.clip(offset - window, 0, k.shape[1] - band)
            kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            k_pos = start + jnp.arange(band, dtype=jnp.int32)
            mask = _mask_block(q_pos, k_pos, causal=causal, window=window)
            return _sdpa_block(qc, kb, vb, mask=mask[None, None, None],
                               cap=cfg.attn_softcap, scale=scale)
        mask = _mask_block(q_pos, key_positions, causal=causal, window=window)
        return _sdpa_block(qc, k, v, mask=mask[None, None, None], cap=cfg.attn_softcap, scale=scale)

    if cfg.remat != "none":
        # flash-style backward: recompute chunk scores instead of saving the
        # (chunk x S) probability tensor per chunk across the scan
        one_chunk = jax.checkpoint(one_chunk)

    if n_chunks == 1:
        out = one_chunk(q, jnp.int32(0))
    elif cfg.unroll_attn_chunks:
        outs = [
            one_chunk(q[:, i * chunk : (i + 1) * chunk], jnp.int32(i * chunk))
            for i in range(n_chunks)
        ]
        out = jnp.concatenate(outs, axis=1)
    else:
        qs = q.reshape(B, n_chunks, chunk, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
        offs = jnp.arange(n_chunks, dtype=jnp.int32) * chunk

        def body(_, xs):
            qc, off = xs
            return None, one_chunk(qc, off)

        _, outs = jax.lax.scan(body, None, (qs, offs))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S_pad, K, G, hd)
    out = out[:, :S]

    y = out.reshape(B, S, H * hd) @ p["wo"]
    y = hint(y, "batch", "seq", None)
    if return_kv:
        return y, (k, v)
    return y


def prefill_cache_from_kv(k, v, cfg: ModelConfig, *, local: bool):
    """Convert full-sequence K/V into the decode cache layout.

    Global: identity (append cache, full S slots).
    Local: ring buffer of the last W positions; slot = pos % W, realised as a
    cyclic roll of the tail (see attn_decode for the inverse mapping).
    """
    if not local:
        return {"k": k, "v": v}
    W = cfg.window_size
    S = k.shape[1]
    if S <= W:
        pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
        return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    shift = (S - W) % W
    return {
        "k": jnp.roll(k[:, -W:], shift, axis=1),
        "v": jnp.roll(v[:, -W:], shift, axis=1),
    }


# ---------------------------------------------------------------------------
# Decode (single token, cached KV)
# ---------------------------------------------------------------------------


def attn_decode(
    p: dict,
    x: jax.Array,
    cache: dict,
    pos: jax.Array,
    cfg: ModelConfig,
    *,
    layer,
    local: bool = False,
):
    """x: (B, 1, d); pos: scalar int32 — the absolute position of this token.

    ``cache`` holds the K/V of every layer of the stack, ``(layers, B, S,
    K, hd)``, and ``layer`` is this layer's index in it. The token's K/V row
    is written into the stacked buffers in place, and the attention reads
    this layer's keys and values from them, so no layer-sized copy is made.
    Returns (y, the stacked cache with the row written)."""
    B = x.shape[0]
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    H = cfg.num_heads
    G = H // K
    q = (x @ p["wq"]).reshape(B, 1, K, G, hd)
    k_new = (x @ p["wk"]).reshape(B, 1, K, hd)
    v_new = (x @ p["wv"]).reshape(B, 1, K, hd)
    if cfg.rope:
        pos_arr = pos[None].astype(jnp.int32)
        q = rope_apply(q.reshape(B, 1, H, hd), pos_arr, cfg.rope_theta).reshape(B, 1, K, G, hd)
        k_new = rope_apply(k_new, pos_arr, cfg.rope_theta)

    S_c = cache["k"].shape[2]
    if local:
        slot = jnp.mod(pos, cfg.window_size)
        # slot i holds the latest position <= pos congruent to i (mod W);
        # negative -> never written.
        i = jnp.arange(S_c, dtype=jnp.int32)
        slot_pos = pos - jnp.mod(pos - i, cfg.window_size)
        valid = slot_pos >= 0
    else:
        slot = pos
        valid = jnp.arange(S_c, dtype=jnp.int32) <= pos

    ks = jax.lax.dynamic_update_slice(cache["k"], k_new[None], (layer, 0, slot, 0, 0))
    vs = jax.lax.dynamic_update_slice(cache["v"], v_new[None], (layer, 0, slot, 0, 0))
    k = jax.lax.dynamic_index_in_dim(ks, layer, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(vs, layer, keepdims=False)

    scale = hd**-0.5
    mask = valid[None, None, None, None, :]  # (1,1,1,1,Sk)
    out = _sdpa_block(q, k, v, mask=mask, cap=cfg.attn_softcap, scale=scale)
    y = out.reshape(B, 1, H * hd) @ p["wo"]
    return y, {"k": ks, "v": vs}


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------


def cross_attn_forward(p: dict, x: jax.Array, enc_k: jax.Array, enc_v: jax.Array, cfg: ModelConfig):
    """x: (B, Sq, d); enc_k/enc_v: (B, Se, K, hd) — precomputed encoder KV.
    Routed through the query-chunked path (a 4k x 4k cross-score tensor per
    layer does not fit; chunking bounds it exactly like self-attention)."""
    return attn_forward(p, x, cfg, external_kv=(enc_k, enc_v))


def cross_kv(p: dict, enc_out: jax.Array, cfg: ModelConfig):
    B, Se, _ = enc_out.shape
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = (enc_out @ p["wk"]).reshape(B, Se, K, hd)
    v = (enc_out @ p["wv"]).reshape(B, Se, K, hd)
    return k, v


# ---------------------------------------------------------------------------
# Dense reference (oracle for tests / kernels)
# ---------------------------------------------------------------------------


def mha_reference(q, k, v, *, causal=True, window=0, cap=0.0, k_valid=None):
    """Unchunked reference: q (B,Sq,H,hd), k/v (B,Sk,K,hd), GQA by repeat."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qr = q.reshape(B, Sq, K, G, hd)
    q_pos = jnp.arange(Sq, dtype=jnp.int32) + (k.shape[1] - Sq)
    k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    mask = _mask_block(q_pos, k_pos, causal=causal, window=window, k_valid=k_valid)
    out = _sdpa_block(qr, k, v, mask=mask[None, None, None], cap=cap, scale=hd**-0.5)
    return out.reshape(B, Sq, H, hd)
