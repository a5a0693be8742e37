"""Mamba (S6) selective-state-space mixer [arXiv:2312.00752], TPU-adapted.

The recurrence h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t x_t is evaluated with a
``lax.scan`` over time carrying h (B, d_inner, d_state); all projections
(in/x/dt/out) are batched matmuls outside the scan, so MXU work dominates and
the scan body is elementwise. ``repro.kernels.ssm_scan`` holds a Pallas
version of the scan (h resident in VMEM across the sequence), tested in
interpret mode and compiled for the TPU in ``tests/test_tpu_compile.py``; no
served path calls it.

With ``cfg.mamba_inner_norms`` the dt, B and C slices of ``x_proj``'s output
each pass an RMSNorm before use, as in Jamba's mixer (HF
``JambaMambaMixer``); ``cfg.mamba_dt_rank`` sets dt's rank (0 derives
``ceil(d_model / 16)``).

Decode carries (conv_state, h) as the layer's cache: O(1) per token, which is
why jamba runs the long_500k cell. Prefill and decode share ``_ssm_core``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.partition import hint

from .layers import rms_norm
from .params import TSpec

__all__ = ["mamba_template", "mamba_cache_template", "mamba_forward", "mamba_decode"]


MAMBA_CHUNK = 128  # steps per chunk of the two-level selective scan


def mamba_template(cfg: ModelConfig) -> dict:
    d, di, n = cfg.d_model, cfg.mamba_d_inner, cfg.mamba_d_state
    dtr, dc = cfg.resolved_dt_rank, cfg.mamba_d_conv
    t = {
        "in_proj": TSpec((d, 2 * di), ("embed", "ff"), init="fan_in"),
        "conv_w": TSpec((dc, di), (None, "ff"), init="normal", std=0.1),
        "conv_b": TSpec((di,), ("ff",), init="zeros"),
        "x_proj": TSpec((di, dtr + 2 * n), ("ff", None), init="fan_in"),
        "dt_proj": TSpec((dtr, di), (None, "ff"), init="fan_in"),
        "dt_bias": TSpec((di,), ("ff",), init="zeros"),
        "A_log": TSpec((di, n), ("ff", None), init="ones"),
        "D": TSpec((di,), ("ff",), init="ones"),
        "out_proj": TSpec((di, d), ("ff", "embed"), init="fan_in"),
    }
    if cfg.mamba_inner_norms:
        t["dt_norm"] = TSpec((dtr,), (None,), init="zeros")
        t["B_norm"] = TSpec((n,), (None,), init="zeros")
        t["C_norm"] = TSpec((n,), (None,), init="zeros")
    return t


def mamba_cache_template(cfg: ModelConfig, batch: int) -> dict:
    di, n, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    return {
        "conv": TSpec((batch, dc - 1, di), ("cache_batch", None, "ff"), init="zeros"),
        "h": TSpec((batch, di, n), ("cache_batch", "ff", None), init="zeros", dtype="float32"),
    }


def _ssm_inputs(p: dict, x: jax.Array, cfg: ModelConfig):
    """Shared input projection: (u, z), u the conv's input and z the gate."""
    xz = x @ p["in_proj"]
    xz = hint(xz, "batch", "seq_inner", "ff")
    u, z = jnp.split(xz, 2, axis=-1)  # (B, S, di)
    return u, z


def _ssm_core(p: dict, u_conv: jax.Array, cfg: ModelConfig, h0: jax.Array):
    """Run the selective scan over u_conv (B, S, di) from initial state h0.
    Returns (y (B,S,di), h_final (B,di,n) fp32)."""
    di, n = cfg.mamba_d_inner, cfg.mamba_d_state
    dtr = cfg.resolved_dt_rank
    dbc = u_conv @ p["x_proj"]  # (B, S, dtr + 2n)
    dt_in, Bc, Cc = jnp.split(dbc, [dtr, dtr + n], axis=-1)
    if cfg.mamba_inner_norms:
        dt_in = rms_norm(dt_in, p["dt_norm"], cfg.norm_eps)
        Bc = rms_norm(Bc, p["B_norm"], cfg.norm_eps)
        Cc = rms_norm(Cc, p["C_norm"], cfg.norm_eps)
    # dt, its bias and softplus, the scan and its output stay in float32,
    # as in the selective-scan kernel
    f32 = jnp.float32
    dt = jax.nn.softplus((dt_in @ p["dt_proj"]).astype(f32) + p["dt_bias"].astype(f32))
    dt = hint(dt, "batch", "seq_inner", "ff")
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # (di, n), negative real

    def step(h, xs_t):
        dt_t, B_t, C_t, u_t = xs_t  # (B, di), (B, n), (B, n), (B, di)
        decay = jnp.exp(dt_t[..., None] * A[None])  # (B, di, n)
        inp = (dt_t * u_t.astype(f32))[..., None] * B_t.astype(f32)[:, None, :]
        h = decay * h + inp
        return h, jnp.einsum("bdn,bn->bd", h, C_t.astype(f32))

    # Two-level scan: an outer scan over chunks of MAMBA_CHUNK steps carries
    # only the (B, di, n) state from chunk to chunk, and an inner scan steps
    # through each chunk. The served prefill's chip numbers were measured
    # with this loop; whether one flat scan is faster is not measured.
    S = u_conv.shape[1]
    tc = min(MAMBA_CHUNK, S)
    while S % tc:
        tc -= 1
    nc = S // tc

    def to_chunks(t):  # (B, S, f) -> (nc, tc, B, f)
        return jnp.swapaxes(t.reshape(t.shape[0], nc, tc, -1), 0, 1).swapaxes(1, 2)

    xs = tuple(to_chunks(t) for t in (dt, Bc, Cc, u_conv))

    def chunk_body(h, xs_chunk):
        return jax.lax.scan(step, h, xs_chunk)

    h_final, y_cm = jax.lax.scan(chunk_body, h0, xs)  # y_cm: (nc, tc, B, di)
    y = jnp.moveaxis(y_cm.reshape(nc * tc, *y_cm.shape[2:]), 0, 1)
    y = hint(y, "batch", "seq_inner", "ff") + u_conv.astype(f32) * p["D"].astype(f32)
    return y, h_final


def _gated_out(p: dict, y: jax.Array, z: jax.Array) -> jax.Array:
    """(y * silu(z)) in float32, then the output projection in z's dtype."""
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype) @ p["out_proj"]


def mamba_forward(
    p: dict, x: jax.Array, cfg: ModelConfig, *, return_cache: bool = False
):
    """x: (B, S, d) -> (B, S, d) [, cache]."""
    B, S, _ = x.shape
    di, dc = cfg.mamba_d_inner, cfg.mamba_d_conv
    u, z = _ssm_inputs(p, x, cfg)
    # causal depthwise conv along seq (kernel dc)
    u_pad = jnp.pad(u, ((0, 0), (dc - 1, 0), (0, 0)))
    f32 = jnp.float32
    u_conv = sum(
        u_pad[:, i : i + S].astype(f32) * p["conv_w"][i].astype(f32) for i in range(dc)
    ) + p["conv_b"].astype(f32)
    u_conv = hint(jax.nn.silu(u_conv).astype(x.dtype), "batch", "seq_inner", "ff")
    h0 = jnp.zeros((B, di, cfg.mamba_d_state), jnp.float32)
    y, h_final = _ssm_core(p, u_conv, cfg, h0)
    out = _gated_out(p, y, z)
    out = hint(out, "batch", "seq", None)
    if not return_cache:
        return out
    # conv cache = last (dc-1) raw conv inputs (pre-activation), as in decode
    cache = {"conv": u_pad[:, S : S + dc - 1, :], "h": h_final}
    return out, cache


def mamba_decode(p: dict, x: jax.Array, cache: dict, cfg: ModelConfig):
    """x: (B, 1, d); cache {conv (B, dc-1, di), h (B, di, n)} -> (y, cache)."""
    B = x.shape[0]
    dc = cfg.mamba_d_conv
    u, z = _ssm_inputs(p, x, cfg)  # (B, 1, di)
    window = jnp.concatenate([cache["conv"], u], axis=1)  # (B, dc, di)
    f32 = jnp.float32
    u_conv = jnp.einsum("bcd,cd->bd", window.astype(f32), p["conv_w"].astype(f32))
    u_conv = jax.nn.silu(u_conv + p["conv_b"].astype(f32)).astype(x.dtype)[:, None, :]
    y, h = _ssm_core(p, u_conv, cfg, cache["h"])
    out = _gated_out(p, y, z)
    return out, {"conv": window[:, 1:, :], "h": h}
