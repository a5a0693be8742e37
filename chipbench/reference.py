"""Plain reference of the served decoders, and its lower-precision control.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``, one full causal
forward pass over a prompt and the tokens that were served for it, with
weights regenerated from the seed (``weights.layer_f32``): it takes nothing
that the program made. The layers are the model family's
(``chipbench/families/<family>.py`` ``hidden``), built from the pieces
below; the final RMSNorm and the untied head are here.

``fp8=True`` is the control: every weight matmul takes both operands through
float8 e4m3 with a per-tensor scale, the step below the served bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families, weights

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512  # queries per attention block
PAD = 512  # rows are padded to a multiple of this (one compile per length)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HI)


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def attention(q, k, v):
    """q: (T, H, hd); k, v: (T, K, hd). Causal softmax attention."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        keep = jnp.arange(T)[None, :] <= (i * Q_CHUNK + jnp.arange(Q_CHUNK))[:, None]
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=HI)

    out = jax.lax.map(block, jnp.arange(T // Q_CHUNK))
    return out.reshape(T, H, hd)


@partial(jax.jit, static_argnums=(3, 4))
def _head(xs, top, targets, eps, fp8):
    """xs: (T, d); targets: (T, J) token ids. Returns, per position, the best
    logit, the logits of the J targets, and the top-ranked token."""
    T, d = xs.shape

    def rows(args):
        xb, tg = args
        logits = mm(rms(xb, top["final_norm"], eps), top["embed/unembed"], fp8)
        return (logits.max(-1), jnp.take_along_axis(logits, tg, axis=-1),
                logits.argmax(-1).astype(jnp.int32))

    n = T // Q_CHUNK
    best, picked, first = jax.lax.map(
        rows, (xs.reshape(n, Q_CHUNK, d), targets.reshape(n, Q_CHUNK, -1)))
    return best.reshape(T), picked.reshape(T, -1), first.reshape(T)


def served_gaps(m: dict, seed: int, prompts: list[np.ndarray],
                served: list[list[int]], *, fp8_control: bool = False):
    """Per request, the gap by which each served token's reference logit
    lies below the reference's best at that position.

    With ``fp8_control`` it also returns, at the same positions, the gap of
    the token that the fp8 control ranks first. Each request's prompt and
    served tokens form one row, padded to a multiple of ``PAD``.
    """
    top = weights.top_f32(m, seed)
    hidden = families.of(m).hidden
    rows, targets, spans = [], [], []
    for p, s in zip(prompts, served):
        L, n = len(p), len(s)
        T = -(-(L + n - 1) // PAD) * PAD
        row = np.zeros(T, np.int32)
        row[:L] = p
        row[L:L + n - 1] = s[:-1]
        tg = np.zeros((T, 2), np.int32)
        tg[L - 1:L - 1 + n, 0] = s
        rows.append(row)
        targets.append(tg)
        spans.append(slice(L - 1, L - 1 + n))
    if fp8_control:
        for r, x in enumerate(hidden(m, seed, rows, top, True)):
            targets[r][:, 1] = np.asarray(
                _head(x, top, jnp.asarray(targets[r]), m["norm_eps"], True)[2])
    gaps, ctl_gaps = [], []
    for r, x in enumerate(hidden(m, seed, rows, top, False)):
        best, picked, _ = (np.asarray(a) for a in
                           _head(x, top, jnp.asarray(targets[r]), m["norm_eps"], False))
        gaps.append(best[spans[r]] - picked[spans[r], 0])
        ctl_gaps.append(best[spans[r]] - picked[spans[r], 1])
    return (gaps, ctl_gaps) if fp8_control else gaps
