"""Model assembly: decoder-only / encoder-decoder LMs over superblock stacks.

One code path serves all 10 assigned architectures; the superblock pattern in
the config decides which mixers/FFNs appear. The stack is scanned over
superblocks (HLO O(1) in depth).

Modes:
  forward  — full-sequence logits (the reference the served modes are
             tested against)
  prefill  — full-sequence + build decode caches
  decode   — one token, update the caches: the stacked caches are the layer
             loop's carry, and each layer writes its one K/V row (or its
             whole recurrent state) at its own index, so a caller that
             donates them has them updated in place

Expert layers route with capacity in ``forward`` (``moe.moe_apply``) and
without drops in ``prefill`` and ``decode`` (``moe.moe_dropless``, which
also serves a share of the experts). Each mixer sub-block (its norm, the
mixer, the residual add) runs under ``jax.named_scope(<mixer kind>)`` and
each FFN sub-block under ``jax.named_scope(<ffn kind>)``, so a profile's ops
map to the layer kind they belong to. The caches of an expert layer hold
``routed``: per batch row, the int32 count of the (token, choice) pairs
that the prefill or decode step which wrote them routed to each held
expert. Nothing reads it back; it is how a step reports its routing
without changing what it returns (:func:`expert_tokens`).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.sharding.partition import hint

from . import attention as A
from . import moe as M
from . import ssm as SSM
from . import xlstm as XL
from .layers import embed_template, mlp_apply, mlp_template, norm_template, rms_norm, softcap
from .params import TSpec, abstract_params, count_params, init_params, param_axes, stack

__all__ = [
    "model_template",
    "cache_template",
    "init_model",
    "abstract_model",
    "model_param_axes",
    "forward",
    "prefill",
    "decode_step",
    "encode",
    "expert_tokens",
]


# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------


def _block_template(cfg: ModelConfig, spec: LayerSpec, *, cross: bool) -> dict:
    d = cfg.d_model
    t: dict[str, Any] = {"norm1": norm_template(d)}
    if spec.mixer in ("attn", "attn_local"):
        t["attn"] = A.attn_template(cfg)
    elif spec.mixer == "mamba":
        t["mamba"] = SSM.mamba_template(cfg)
    elif spec.mixer == "mlstm":
        t["mlstm"] = XL.mlstm_template(cfg)
    elif spec.mixer == "slstm":
        t["slstm"] = XL.slstm_template(cfg)
    else:
        raise ValueError(spec.mixer)
    if cross and spec.mixer in ("attn", "attn_local"):
        t["norm_cross"] = norm_template(d)
        t["cross"] = A.attn_template(cfg)
    if spec.ffn in ("mlp", "moe", "moe_dense"):
        t["norm2"] = norm_template(d)
    if spec.ffn == "mlp":
        t["mlp"] = mlp_template(cfg)
    elif spec.ffn == "moe":
        t["moe"] = M.moe_template(cfg)
    elif spec.ffn == "moe_dense":
        t["moe"] = M.moe_template(cfg)
        t["dense_mlp"] = mlp_template(cfg)
    return t


def model_template(cfg: ModelConfig) -> dict:
    blocks = tuple(
        _block_template(cfg, spec, cross=cfg.is_encdec) for spec in cfg.superblock
    )
    t: dict[str, Any] = {
        "embed": embed_template(cfg),
        "blocks": stack(blocks, cfg.num_superblocks),
        "final_norm": norm_template(cfg.d_model),
    }
    if cfg.is_encdec:
        enc_block = {
            "norm1": norm_template(cfg.d_model),
            "attn": A.attn_template(cfg),
            "norm2": norm_template(cfg.d_model),
            "mlp": mlp_template(cfg),
        }
        t["encoder"] = {
            "blocks": stack((enc_block,), cfg.encoder_layers),
            "final_norm": norm_template(cfg.d_model),
        }
    return t


def cache_template(
    cfg: ModelConfig, batch: int, cache_len: int, *, enc_len: int = 0
) -> tuple:
    """Decode-cache template: tuple over superblock positions, leaves stacked
    over num_superblocks."""
    per_pos = []
    for spec in cfg.superblock:
        c: dict[str, Any] = {}
        if spec.mixer in ("attn", "attn_local"):
            c.update(
                A.kv_cache_template(cfg, batch, cache_len, local=spec.mixer == "attn_local")
            )
            if cfg.is_encdec:
                K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
                shape = (batch, enc_len, K, hd)
                axes = ("cache_batch", "cache_seq", None, None)
                c["cross_k"] = TSpec(shape, axes, init="zeros")
                c["cross_v"] = TSpec(shape, axes, init="zeros")
        elif spec.mixer == "mamba":
            c.update(SSM.mamba_cache_template(cfg, batch))
        elif spec.mixer == "mlstm":
            c.update(XL.mlstm_cache_template(cfg, batch))
        elif spec.mixer == "slstm":
            c.update(XL.slstm_cache_template(cfg, batch))
        if spec.ffn in ("moe", "moe_dense"):
            c["routed"] = TSpec((batch, cfg.held_experts), ("cache_batch", None),
                                init="zeros", dtype="int32")
        per_pos.append(c)
    return stack(tuple(per_pos), cfg.num_superblocks)


def init_model(cfg: ModelConfig, key: jax.Array):
    return init_params(model_template(cfg), key, jnp.dtype(cfg.dtype))


def abstract_model(cfg: ModelConfig):
    return abstract_params(model_template(cfg), jnp.dtype(cfg.dtype))


def model_param_axes(cfg: ModelConfig):
    return param_axes(model_template(cfg))


def num_params(cfg: ModelConfig) -> int:
    return count_params(model_template(cfg))


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _apply_ffn(spec: LayerSpec, p: dict, x: jax.Array, cfg: ModelConfig, mode: str):
    """Returns (x, (batch, held) pairs routed to each held expert, or None)."""
    if spec.ffn == "none":
        return x, None
    h = rms_norm(x, p["norm2"], cfg.norm_eps)
    if spec.ffn == "mlp":
        return x + mlp_apply(p["mlp"], h, cfg), None
    if spec.ffn not in ("moe", "moe_dense"):
        raise ValueError(spec.ffn)
    if mode == "forward":
        y, routed = M.moe_apply(p["moe"], h, cfg), None
    else:
        y, routed = M.moe_dropless(p["moe"], h, cfg)
    if spec.ffn == "moe_dense":  # arctic: routed experts + parallel dense MLP
        y = y + mlp_apply(p["dense_mlp"], h, cfg)
    return x + y, routed


def _apply_block(
    spec: LayerSpec,
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    mode: str,
    cache: dict | None,
    pos,
    enc_out,
    causal: bool,
    cross: bool = False,
    layer=None,
):
    """Returns (x, new_cache_or_None). In decode mode ``cache`` holds the
    caches of every layer of the stack at this superblock position and
    ``layer`` is this layer's index in them; the returned caches are the
    stack's, with this layer's written (:func:`_store`)."""
    with jax.named_scope(spec.mixer):
        x, new_cache = _apply_mixer(spec, p, x, cfg, mode=mode, cache=cache, pos=pos,
                                    enc_out=enc_out, causal=causal, cross=cross, layer=layer)
    with jax.named_scope(spec.ffn):
        x, routed = _apply_ffn(spec, p, x, cfg, mode)
    if routed is not None:
        new_cache["routed"] = routed
    x = hint(x, "batch", "seq", None)
    if mode == "decode":
        return x, _store(cache, new_cache, layer)
    return x, (new_cache if new_cache else None)


def _store(stacked: dict, written: dict, layer) -> dict:
    """The stack's caches after one decode layer wrote ``written``.

    A K/V leaf comes back as the stacked buffer, its one new row already
    written in place (``attn_decode``), and is taken as it is. A state leaf
    (Mamba ``conv``/``h``, xLSTM states, an expert layer's ``routed``) comes
    back as the layer's new value, which replaces its slice whole. A leaf the
    layer only reads (the encoder's ``cross_k``/``cross_v``) is not written.
    """
    out = dict(stacked)
    for k, v in written.items():
        full = stacked[k]
        out[k] = v if v.ndim == full.ndim else jax.lax.dynamic_update_index_in_dim(full, v, layer, 0)
    return out


def _apply_mixer(spec: LayerSpec, p: dict, x: jax.Array, cfg: ModelConfig, *, mode: str,
                 cache, pos, enc_out, causal: bool, cross: bool, layer=None):
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    new_cache: dict[str, Any] = {}
    if mode == "decode":  # this layer's slices, read where used (XLA drops the rest)
        state = {k: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False) for k, c in cache.items()}
    if spec.mixer in ("attn", "attn_local"):
        local = spec.mixer == "attn_local"
        if mode == "decode":
            y, kv = A.attn_decode(p["attn"], h, cache, pos, cfg, layer=layer, local=local)
            new_cache.update(kv)
        elif mode == "prefill":
            y, (k, v) = A.attn_forward(p["attn"], h, cfg, causal=causal, local=local, return_kv=True)
            new_cache.update(A.prefill_cache_from_kv(k, v, cfg, local=local))
        else:
            y = A.attn_forward(p["attn"], h, cfg, causal=causal, local=local)
        x = x + y
        if cross:
            hc = rms_norm(x, p["norm_cross"], cfg.norm_eps)
            if mode == "decode":
                ck, cv = state["cross_k"], state["cross_v"]
            else:
                ck, cv = A.cross_kv(p["cross"], enc_out, cfg)
            x = x + A.cross_attn_forward(p["cross"], hc, ck, cv, cfg)
            if mode == "prefill":
                new_cache["cross_k"], new_cache["cross_v"] = ck, cv
    elif spec.mixer == "mamba":
        if mode == "decode":
            y, c = SSM.mamba_decode(p["mamba"], h, state, cfg)
            new_cache.update(c)
        elif mode == "prefill":
            y, c = SSM.mamba_forward(p["mamba"], h, cfg, return_cache=True)
            new_cache.update(c)
        else:
            y = SSM.mamba_forward(p["mamba"], h, cfg)
        x = x + y
    elif spec.mixer == "mlstm":
        if mode == "decode":
            y, c = XL.mlstm_decode(p["mlstm"], h, state, cfg)
            new_cache.update(c)
        elif mode == "prefill":
            y, c = XL.mlstm_forward(p["mlstm"], h, cfg, return_cache=True)
            new_cache.update(c)
        else:
            y = XL.mlstm_forward(p["mlstm"], h, cfg)
        x = x + y
    elif spec.mixer == "slstm":
        if mode == "decode":
            y, c = XL.slstm_decode(p["slstm"], h, state, cfg)
            new_cache.update(c)
        elif mode == "prefill":
            y, c = XL.slstm_forward(p["slstm"], h, cfg, return_cache=True)
            new_cache.update(c)
        else:
            y = XL.slstm_forward(p["slstm"], h, cfg)
        x = x + y
    else:
        raise ValueError(spec.mixer)
    return x, new_cache


# ---------------------------------------------------------------------------
# Stack runner
# ---------------------------------------------------------------------------


def _run_stack(
    blocks_params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    mode: str,
    caches=None,
    pos=None,
    enc_out=None,
    causal: bool = True,
    cross: bool = False,
    superblock=None,
):
    superblock = superblock or cfg.superblock

    def body_fn(x, block_params, block_caches, layer=None):
        new_caches = []
        for i, spec in enumerate(superblock):
            c = block_caches[i] if block_caches is not None else None
            x, nc = _apply_block(
                spec, block_params[i], x, cfg, mode=mode, cache=c, pos=pos,
                enc_out=enc_out, causal=causal, cross=cross, layer=layer,
            )
            new_caches.append(nc)
        return x, tuple(new_caches)

    if mode == "decode":
        # The stacked caches ride in the loop's carry: each layer writes its
        # K/V row and its state into them at its own index, so a donated
        # cache is updated in place and never copied out of the stack.
        def decode_body(carry, xs_t):
            x, caches = carry
            bp, layer = xs_t
            return body_fn(x, bp, caches, layer), None

        layers = jnp.arange(cfg.num_superblocks, dtype=jnp.int32)
        (x, caches), _ = jax.lax.scan(decode_body, (x, caches), (blocks_params, layers))
        return x, caches

    emit_cache = mode == "prefill"

    def scan_body(carry, bp):
        y, ncs = body_fn(carry, bp, None)
        return y, (ncs if emit_cache else None)

    x, new_caches = jax.lax.scan(scan_body, x, blocks_params)
    return x, new_caches


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def _embed(params, tokens, cfg: ModelConfig, prefix_embeds=None):
    emb = params["embed"]["embedding"]
    x = jnp.take(emb, tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:  # gemma-style input scaling
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return hint(x, "batch", "seq", None)


def _head(params, x, cfg: ModelConfig):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["embedding"].T
    else:
        logits = x @ params["embed"]["unembed"]
    logits = softcap(logits, cfg.final_softcap)
    return hint(logits, "batch", "seq_inner", "vocab")


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def encode(params, cfg: ModelConfig, enc_embeds: jax.Array) -> jax.Array:
    """Encoder stack over stubbed frontend embeddings (B, Se, d)."""
    enc = params["encoder"]
    x = hint(enc_embeds.astype(jnp.dtype(cfg.dtype)), "batch", "seq", None)
    x, _ = _run_stack(
        enc["blocks"], x, cfg, mode="forward", causal=False,
        superblock=(LayerSpec("attn", "mlp"),),
    )
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, tokens, *, prefix_embeds=None, enc_embeds=None):
    """Full-sequence logits: the reference that prefill and decode are tested against."""
    enc_out = encode(params, cfg, enc_embeds) if cfg.is_encdec else None
    x = _embed(params, tokens, cfg, prefix_embeds)
    x, _ = _run_stack(params["blocks"], x, cfg, mode="forward", enc_out=enc_out,
                      cross=cfg.is_encdec)
    return _head(params, x, cfg)


def prefill(params, cfg: ModelConfig, tokens, *, prefix_embeds=None, enc_embeds=None):
    """Full-sequence forward that also builds decode caches.
    Returns (last-position logits, caches)."""
    enc_out = encode(params, cfg, enc_embeds) if cfg.is_encdec else None
    x = _embed(params, tokens, cfg, prefix_embeds)
    x, caches = _run_stack(params["blocks"], x, cfg, mode="prefill", enc_out=enc_out,
                           cross=cfg.is_encdec)
    logits = _head(params, x[:, -1:, :], cfg)
    return logits, caches


def decode_step(params, cfg: ModelConfig, token, pos, caches):
    """token: (B, 1) int32; pos: scalar int32 absolute position.
    Returns (logits (B,1,V), new caches)."""
    x = _embed(params, token, cfg)
    x, new_caches = _run_stack(
        params["blocks"], x, cfg, mode="decode", caches=caches, pos=pos,
        cross=cfg.is_encdec,
    )
    return _head(params, x, cfg), new_caches


def expert_tokens(cfg: ModelConfig, caches) -> jax.Array:
    """(expert layers, experts held) int32, layers in stack order: the
    (token, choice) pairs that the step which wrote ``caches`` routed to
    each held expert, summed over the batch; (0, 0) for a model without
    experts."""
    per_pos = [c["routed"] for c in caches if c and "routed" in c]  # (n_sb, batch, held)
    if not per_pos:
        return jnp.zeros((0, 0), jnp.int32)
    return jnp.stack(per_pos, axis=1).sum(axis=2).reshape(-1, cfg.held_experts)

