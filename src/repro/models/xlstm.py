"""xLSTM blocks [arXiv:2405.04517]: mLSTM (matrix memory) + sLSTM (scalar).

TPU adaptation (DESIGN.md §5): the mLSTM is evaluated in *chunkwise-parallel*
form — within a chunk the contribution matrix is an attention-like matmul
(MXU-friendly), across chunks a small fp32 state (C, n) is carried by a
``lax.scan``. Stability: sigmoid forget gate (log-space cumsum, decay factors
<= 1) and a capped exponential input gate; the normalizer uses the paper's
max(|q.n|, 1) lower bound, so no stabiliser-max bookkeeping is needed.

The sLSTM has true hidden-to-gate recurrence (R matrices) and is inherently
sequential: a per-timestep scan with the paper's m-stabilised exponential
gating. Both cells expose O(1)-state decode paths (-> long_500k runs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.partition import hint

from .layers import rms_norm
from .params import TSpec

__all__ = [
    "mlstm_template",
    "slstm_template",
    "mlstm_cache_template",
    "slstm_cache_template",
    "mlstm_forward",
    "mlstm_decode",
    "slstm_forward",
    "slstm_decode",
]

_ILOG_CAP = 8.0  # cap on the exponential input gate pre-activation
MLSTM_CHUNK = 256


def mlstm_template(cfg: ModelConfig) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    return {
        "wq": TSpec((d, d), ("embed", "qkv"), init="fan_in"),
        "wk": TSpec((d, d), ("embed", "qkv"), init="fan_in"),
        "wv": TSpec((d, d), ("embed", "qkv"), init="fan_in"),
        "w_if": TSpec((d, 2 * H), ("embed", None), init="normal", std=0.01),
        "b_if": TSpec((2 * H,), (None,), init="zeros"),
        "w_og": TSpec((d, d), ("embed", "qkv"), init="fan_in"),
        "headnorm": TSpec((d,), ("embed",), init="zeros"),
        "wo": TSpec((d, d), ("qkv", "embed"), init="fan_in"),
    }


def slstm_template(cfg: ModelConfig) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    hd = d // H
    return {
        "w_in": TSpec((d, 4 * d), ("embed", "qkv"), init="fan_in"),
        "r": TSpec((H, hd, 4 * hd), (None, None, None), init="normal", std=0.01),
        "b": TSpec((4 * d,), (None,), init="zeros"),
        "headnorm": TSpec((d,), ("embed",), init="zeros"),
        "wo": TSpec((d, d), ("qkv", "embed"), init="fan_in"),
    }


def mlstm_cache_template(cfg: ModelConfig, batch: int) -> dict:
    H = cfg.num_heads
    hd = cfg.d_model // H
    return {
        "C": TSpec((batch, H, hd, hd), ("cache_batch", None, "mlstm_dk", None), init="zeros", dtype="float32"),
        "n": TSpec((batch, H, hd), ("cache_batch", None, "mlstm_dk"), init="zeros", dtype="float32"),
    }


def slstm_cache_template(cfg: ModelConfig, batch: int) -> dict:
    d = cfg.d_model
    z = dict(init="zeros", dtype="float32")
    return {
        "c": TSpec((batch, d), ("cache_batch", None), **z),
        "n": TSpec((batch, d), ("cache_batch", None), **z),
        "h": TSpec((batch, d), ("cache_batch", None), **z),
        "m": TSpec((batch, d), ("cache_batch", None), **z),
    }


# ---------------------------------------------------------------------------
# mLSTM — chunkwise parallel
# ---------------------------------------------------------------------------


def _mlstm_qkv_gates(p: dict, x: jax.Array, cfg: ModelConfig):
    B, S, d = x.shape
    H = cfg.num_heads
    hd = d // H
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, H, hd) * (hd**-0.5)
    v = (x @ p["wv"]).reshape(B, S, H, hd)
    gates = x @ p["w_if"] + p["b_if"]  # (B, S, 2H)
    ilog = jnp.minimum(gates[..., : H].astype(jnp.float32), _ILOG_CAP)
    flog = -jax.nn.softplus(-gates[..., H :].astype(jnp.float32))  # log sigmoid
    og = jax.nn.sigmoid(x @ p["w_og"])  # (B, S, d)
    return q, k, v, ilog, flog, og


def _mlstm_finish(p: dict, h: jax.Array, og: jax.Array, cfg: ModelConfig):
    B, S = h.shape[0], h.shape[1]
    d = cfg.d_model
    hn = rms_norm(h.reshape(B, S, d), p["headnorm"], cfg.norm_eps)
    out = (hn * og) @ p["wo"]
    return hint(out, "batch", "seq", None)


def _mlstm_chunk(carry, xs):
    """One chunk of the chunkwise-parallel mLSTM. carry: (C, n) fp32.
    xs: q, k, v (B, L, H, hd); ilog, flog (B, L, H)."""
    C0, n0 = carry
    q, k, v, ilog, flog = xs
    b = jnp.cumsum(flog, axis=1)  # (B, L, H), <= 0, decreasing
    # intra-chunk weights w[t, tau] = exp(b_t - b_tau + ilog_tau), tau <= t
    L = q.shape[1]
    decay = b[:, :, None, :] - b[:, None, :, :] + ilog[:, None, :, :]  # (B, t, tau, H)
    tri = jnp.tril(jnp.ones((L, L), bool))
    w = jnp.where(tri[None, :, :, None], jnp.exp(decay), 0.0)  # (B, t, tau, H)
    scores = jnp.einsum("bthd,bshd->btsh", q.astype(jnp.float32), k.astype(jnp.float32))
    ws = w * scores
    num_intra = jnp.einsum("btsh,bshd->bthd", ws, v.astype(jnp.float32))
    den_intra = jnp.sum(ws, axis=2)  # (B, t, H)
    eb = jnp.exp(b)  # (B, L, H)
    num_inter = jnp.einsum("bthd,bhde->bthe", q.astype(jnp.float32), C0) * eb[..., None]
    den_inter = jnp.einsum("bthd,bhd->bth", q.astype(jnp.float32), n0) * eb
    den = jnp.maximum(jnp.abs(den_intra + den_inter), 1.0)
    h = (num_intra + num_inter) / den[..., None]  # (B, L, H, hd)
    # state to end of chunk
    wL = jnp.exp(b[:, -1:, :] - b + ilog)  # (B, L, H): decay from tau to L
    C1 = jnp.exp(b[:, -1])[:, :, None, None] * C0 + jnp.einsum(
        "blh,blhd,blhe->bhde", wL, k.astype(jnp.float32), v.astype(jnp.float32)
    )
    n1 = jnp.exp(b[:, -1])[..., None] * n0 + jnp.einsum(
        "blh,blhd->bhd", wL, k.astype(jnp.float32)
    )
    return (C1, n1), h


def mlstm_forward(p: dict, x: jax.Array, cfg: ModelConfig, *, return_cache: bool = False):
    B, S, d = x.shape
    H = cfg.num_heads
    hd = d // H
    q, k, v, ilog, flog, og = _mlstm_qkv_gates(p, x, cfg)
    L = min(MLSTM_CHUNK, S)
    while S % L:  # largest divisor <= MLSTM_CHUNK (exact chunking)
        L -= 1
    nc = S // L
    C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    n0 = jnp.zeros((B, H, hd), jnp.float32)

    def chunked(t):  # (B, S, ...) -> (nc, B, L, ...)
        return jnp.swapaxes(t.reshape(B, nc, L, *t.shape[2:]), 0, 1)

    xs = tuple(chunked(t) for t in (q, k, v, ilog, flog))
    (C1, n1), hs = jax.lax.scan(_mlstm_chunk, (C0, n0), xs)
    h = jnp.swapaxes(hs, 0, 1).reshape(B, S, H, hd).astype(x.dtype)
    out = _mlstm_finish(p, h, og, cfg)
    if not return_cache:
        return out
    return out, {"C": C1, "n": n1}


def mlstm_decode(p: dict, x: jax.Array, cache: dict, cfg: ModelConfig):
    """x: (B, 1, d). Linear-space single-step update."""
    B = x.shape[0]
    H = cfg.num_heads
    hd = cfg.d_model // H
    q, k, v, ilog, flog, og = _mlstm_qkv_gates(p, x, cfg)
    i = jnp.exp(ilog[:, 0])  # (B, H)
    f = jnp.exp(flog[:, 0])
    kf = k[:, 0].astype(jnp.float32)
    vf = v[:, 0].astype(jnp.float32)
    C = f[..., None, None] * cache["C"] + i[..., None, None] * (
        kf[..., :, None] * vf[..., None, :]
    )
    n = f[..., None] * cache["n"] + i[..., None] * kf
    qf = q[:, 0].astype(jnp.float32)
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n)), 1.0)
    h = (num / den[..., None]).reshape(B, 1, cfg.d_model).astype(x.dtype)
    out = _mlstm_finish(p, h, og, cfg)
    return out, {"C": C, "n": n}


# ---------------------------------------------------------------------------
# sLSTM — sequential with m-stabilised exponential gating
# ---------------------------------------------------------------------------


def _slstm_step(p, cfg, carry, zifo_t):
    """carry: (c, n, h, m) each (B, d) fp32; zifo_t: (B, 4d) input projection."""
    c, n, h, m = carry
    B = c.shape[0]
    d = cfg.d_model
    H = cfg.num_heads
    hd = d // H
    rec = jnp.einsum(
        "bhd,hdf->bhf", h.reshape(B, H, hd).astype(p["r"].dtype), p["r"]
    ).reshape(B, 4 * d)
    g = (zifo_t + rec).astype(jnp.float32)
    zt, it, ft, ot = jnp.split(g, 4, axis=-1)
    z = jnp.tanh(zt)
    m_new = jnp.maximum(ft + m, it)
    i = jnp.exp(it - m_new)
    f = jnp.exp(ft + m - m_new)
    c = f * c + i * z
    n = f * n + i
    h_new = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1e-6)
    return (c, n, h_new, m_new)


def slstm_forward(p: dict, x: jax.Array, cfg: ModelConfig, *, return_cache: bool = False):
    B, S, d = x.shape
    zifo = x @ p["w_in"] + p["b"]  # (B, S, 4d)
    zifo_tm = jnp.swapaxes(zifo, 0, 1)
    zeros = jnp.zeros((B, d), jnp.float32)
    init = (zeros, zeros, zeros, jnp.full((B, d), -1e30, jnp.float32))

    def step(carry, zt):
        new = _slstm_step(p, cfg, carry, zt)
        return new, new[2]  # emit h

    carry, hs = jax.lax.scan(step, init, zifo_tm)
    h = jnp.swapaxes(hs, 0, 1).astype(x.dtype)
    hn = rms_norm(h, p["headnorm"], cfg.norm_eps)
    out = hint(hn @ p["wo"], "batch", "seq", None)
    if not return_cache:
        return out
    c, n, hh, m = carry
    return out, {"c": c, "n": n, "h": hh, "m": m}


def slstm_decode(p: dict, x: jax.Array, cache: dict, cfg: ModelConfig):
    B = x.shape[0]
    zifo = (x @ p["w_in"] + p["b"])[:, 0]  # (B, 4d)
    carry = (cache["c"], cache["n"], cache["h"], cache["m"])
    c, n, h, m = _slstm_step(p, cfg, carry, zifo)
    hn = rms_norm(h[:, None, :].astype(x.dtype), p["headnorm"], cfg.norm_eps)
    out = hn @ p["wo"]
    return out, {"c": c, "n": n, "h": h, "m": m}
