"""Mixture-of-Experts layers: token-choice top-k routing.

Two layers share one router (softmax over all ``num_experts`` in float32,
top-k, the chosen gates renormalised to sum to 1 unless
``cfg.moe_renormalize`` is off):

``moe_apply`` (training)
    GShard capacity routing ([arXiv:2006.16668]): dense one-hot
    dispatch/combine einsums, experts sharded over the "expert" logical axis
    (an all-to-all under GSPMD) and each expert's hidden dim over
    "expert_ff". Tokens are split into dispatch groups of
    ``moe_group_size`` so the (group, E, capacity) one-hot stays bounded;
    tokens past an expert's capacity are dropped. It needs every expert.
``moe_dropless`` (serving: ``lm.prefill`` and ``lm.decode_step``)
    no capacity and no drops: a token's output never depends on its batch
    companions. The parameters hold experts ``expert_offset`` ..
    ``expert_offset + held_experts - 1``, one chip's share under expert
    parallelism; the router still scores all ``num_experts``, and pairs
    routed to experts held elsewhere add nothing here. The (token, choice)
    pairs on held experts are sorted by expert and run as grouped matmuls
    (``jax.lax.ragged_dot``), then scattered back weighted by their gates.
    With no more pairs than held experts (a decode step) each pair instead
    reads its own expert's weights by a dynamic index under a ``lax.cond``.
    Both read only the experts that got a pair; on a v5e at Jamba2-Mini's
    widths the per-pair path takes 0.49 ms for one hit expert and 0.96 ms
    for two against ``ragged_dot``'s 0.52 and 0.98, and its ops keep the
    layer's ``jax.named_scope``, which the TPU's grouped-matmul fusions drop.

Variants:
  "moe"       — routed experts only (dbrx, jamba)
  "moe_dense" — routed experts + parallel dense residual MLP (arctic)
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.partition import hint

from .layers import _act, mlp_apply, mlp_template
from .params import TSpec

__all__ = ["moe_template", "moe_apply", "moe_dropless", "capacity"]


def moe_template(cfg: ModelConfig) -> dict:
    d, f, e, h = cfg.d_model, cfg.d_ff, cfg.num_experts, cfg.held_experts
    return {
        "router": TSpec((d, e), ("embed", "expert"), init="fan_in"),
        "wi": TSpec((h, d, f), ("expert", "embed", "expert_ff"), init="fan_in"),
        "wg": TSpec((h, d, f), ("expert", "embed", "expert_ff"), init="fan_in"),
        "wo": TSpec((h, f, d), ("expert", "expert_ff", "embed"), init="fan_in"),
    }


def _route(router: jax.Array, x: jax.Array, cfg: ModelConfig):
    """x: (..., d) -> (gates, expert ids) of shape (..., k): the top-k of a
    float32 softmax over all ``num_experts``."""
    logits = jnp.einsum("...d,de->...e", x, router, preferred_element_type=jnp.float32)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
    if cfg.moe_renormalize:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx


def _largest_divisor(n: int, upper: int) -> int:
    """Largest divisor of n that is <= upper (group tokens exactly)."""
    for s in range(upper, 0, -1):
        if n % s == 0:
            return s
    return 1


def capacity(cfg: ModelConfig, group_tokens: int) -> int:
    """Per-group per-expert capacity C = ceil(k * s * cf / E), MXU-aligned."""
    c = math.ceil(
        cfg.num_experts_per_tok * group_tokens * cfg.capacity_factor / cfg.num_experts
    )
    return max(4, ((c + 3) // 4) * 4)


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, d) -> (B, S, d). Routed top-k with capacity dropping."""
    B, S, d = x.shape
    E, topk = cfg.num_experts, cfg.num_experts_per_tok
    if cfg.held_experts != E:
        raise ValueError("capacity routing needs every expert; serve a share with moe_dropless")
    n = B * S
    s = _largest_divisor(n, min(cfg.moe_group_size, n))
    g = n // s
    C = capacity(cfg, s)

    xt = x.reshape(g, s, d)
    gate_vals, expert_idx = _route(p["router"], xt, cfg)  # (g, s, topk)

    # position of each (token, slot) inside its expert's buffer
    onehot_e = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (g, s, topk, E)
    flat = onehot_e.reshape(g, s * topk, E)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat  # (g, s*topk, E)
    pos = jnp.sum(pos_in_expert * flat, axis=-1)  # (g, s*topk)
    keep = (pos < C).reshape(g, s, topk)
    pos = pos.reshape(g, s, topk)
    # Build dispatch/combine per k-slot, accumulating in the model dtype: the
    # (g, s, E, C) one-hot products are the layer's biggest tensors and fp32
    # materialisation of the (g, s*topk, E, C) variant costs 4x the memory.
    disp = jnp.zeros((g, s, E, C), x.dtype)
    comb = jnp.zeros((g, s, E, C), x.dtype)
    for kk in range(topk):
        oe = (onehot_e[:, :, kk] * keep[:, :, kk, None]).astype(x.dtype)  # (g,s,E)
        oc = jax.nn.one_hot(pos[:, :, kk].astype(jnp.int32), C, dtype=x.dtype)
        slot = jnp.einsum("gse,gsc->gsec", oe, oc)
        disp = disp + slot
        comb = comb + slot * gate_vals[:, :, kk, None, None].astype(x.dtype)
    disp = hint(disp, "batch", None, "expert", None)
    comb = hint(comb, "batch", None, "expert", None)

    expert_in = jnp.einsum("gsec,gsd->egcd", disp, xt)
    expert_in = hint(expert_in, "expert", "batch", None, None)
    act = _act(cfg.mlp_act)
    h = jnp.einsum("egcd,edf->egcf", expert_in, p["wi"])
    h = act(jnp.einsum("egcd,edf->egcf", expert_in, p["wg"])) * h
    h = hint(h, "expert", "batch", None, None)
    expert_out = jnp.einsum("egcf,efd->egcd", h, p["wo"])
    out = jnp.einsum("gsec,egcd->gsd", comb, expert_out)
    return out.reshape(B, S, d)


def _expert(x: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array, cfg: ModelConfig):
    return (_act(cfg.mlp_act)(x @ wg) * (x @ wi)) @ wo


def moe_dropless(p: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> ((B, S, d) the held experts' part of the layer's
    output, (B, held_experts) int32: per batch row, the count of its
    (token, choice) pairs routed to each held expert)."""
    B, S, d = x.shape
    H, k = cfg.held_experts, cfg.num_experts_per_tok
    xt = x.reshape(B * S, d)
    gates, idx = _route(p["router"], xt, cfg)  # (n, k)
    local = idx - cfg.expert_offset
    held = (local >= 0) & (local < H)
    group = jnp.where(held, local, H).reshape(-1)  # absent pairs sort last
    routed = jnp.sum(jax.nn.one_hot(group.reshape(B, -1), H, dtype=jnp.int32), axis=1)
    gates = jnp.where(held, gates, 0.0).reshape(-1)
    pairs = group.shape[0]

    if pairs <= H:
        def pair(j):
            def run():
                w = [jax.lax.dynamic_index_in_dim(p[n], group[j], keepdims=False)
                     for n in ("wi", "wg", "wo")]
                return _expert(xt[j // k][None], *w, cfg)[0].astype(jnp.float32)
            return jax.lax.cond(group[j] < H, run, lambda: jnp.zeros((d,), jnp.float32))

        ys = jnp.stack([pair(j) for j in range(pairs)]) * gates[:, None]
        out = jnp.sum(ys.reshape(-1, k, d), axis=1)
    else:
        order = jnp.argsort(group, stable=True)
        tok = order // k
        rows = jnp.take(xt, tok, axis=0)
        sizes = jnp.sum(routed, axis=0)
        h = jax.lax.ragged_dot(rows, p["wi"], sizes)
        g = jax.lax.ragged_dot(rows, p["wg"], sizes)
        y = jax.lax.ragged_dot(_act(cfg.mlp_act)(g) * h, p["wo"], sizes)
        # rows past the held pairs belong to no group: their gate is 0
        gate = gates[order][:, None]
        y = jnp.where(gate > 0, y.astype(jnp.float32) * gate, 0.0)
        out = jnp.zeros((B * S, d), jnp.float32).at[tok].add(y)
    return out.astype(x.dtype).reshape(B, S, d), routed
