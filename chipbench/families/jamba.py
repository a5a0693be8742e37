"""The jamba family: AI21's Jamba (HF ``JambaForCausalLM``) as the program
serves it, one chip's share of it.

A superblock holds the positions that the model block's ``layers`` lists,
each a token mixer and an FFN with weights of their own
(``blocks/<i>/...``). Every sub-block is pre-norm, RMSNorm with
``(1 + scale)``, and adds its output to the residual stream:

- ``mamba``: the Mamba-1 mixer of HF ``JambaMambaMixer``. ``in_proj`` (no
  bias) gives the input ``u`` and the gate ``z``; a causal depthwise conv of
  width ``mamba_d_conv`` with a bias, then SiLU; ``x_proj`` (no bias) gives
  dt (rank ``mamba_dt_rank``), B and C, each through an RMSNorm; ``dt =
  softplus(dt @ dt_proj + dt_bias)``; the selective scan ``h_t = exp(dt_t
  A) h_{t-1} + dt_t u_t B_t`` with ``A = -exp(A_log)``, ``y_t = h_t C_t +
  D u_t``, here a plain sequential recurrence; ``out_proj((y * silu(z)))``.
- ``attn``: causal GQA attention with no positional encoding (NoPE).
- ``mlp``: SwiGLU, ``(silu(x wg) * (x wi)) wo``.
- ``moe``: a float32 softmax over all ``num_experts`` router outputs, the
  top ``num_experts_per_tok``, gates renormalised only with
  ``moe_renormalize``; this chip holds experts ``expert_offset`` ..
  ``expert_offset + experts_held - 1``. The reference sums every held
  expert's SwiGLU output weighted by the gate it got (0 where it was not
  chosen); pairs routed to experts held elsewhere add nothing, in the
  program and here alike.

The reference draws one superblock position's weights at a time, with the
keys of ``weights.served_jit`` (bit for bit); the experts stay in the
served dtype and are widened one at a time, so that it never holds more
than one position's weights (a whole superblock of Jamba2-Mini's share is
29 GB in float32).

The arithmetic counts what a step needs: in a decode step, the held
experts that the tick's counters say were hit (``Engine.expert_tokens``:
per expert layer, the (token, choice) pairs routed to each held expert),
every other weight, the valid keys and values, and the Mamba state read and
written; a prefill token counts the expected ``k * held / E`` held experts
per expert layer, since prefill has no counters.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import HI, attention, mm, rms

PROGRAM_KEYS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff", "vocab_size",
                "num_superblocks", "num_experts", "num_experts_per_tok", "expert_offset",
                "experts_held", "moe_renormalize", "mamba_d_state", "mamba_d_conv",
                "mamba_expand", "mamba_dt_rank", "norm_eps", "dtype")
EXPERT_LEAVES = ("moe/wi", "moe/wg", "moe/wo")
# spreads of the Mamba leaves that have no fan-in: A = -exp(A_log) in
# -[0.4, 2.4], dt's bias and D centred on 0; the depthwise conv's weights
# have PyTorch's default Conv1d spread, (3 * d_conv) ** -0.5
A_LOG_STD = 0.5
DT_BIAS_STD = 0.5
D_STD = 0.5
CONV_BIAS_STD = 0.1


def program_config(m: dict):
    from repro.configs.base import LayerSpec, ModelConfig

    return ModelConfig(name=m["name"], family="hybrid",
                       superblock=tuple(LayerSpec(*k) for k in m["layers"]), rope=False,
                       gated_mlp=True, mlp_act="silu", mamba_inner_norms=True,
                       **{k: m[k] for k in PROGRAM_KEYS})


def _sizes(m: dict):
    d = m["d_model"]
    return d, m["mamba_expand"] * d, m["mamba_d_state"], m["mamba_d_conv"], m["mamba_dt_rank"]


def layout(m: dict) -> dict[str, tuple[tuple[int, ...], bool, float]]:
    d, di, n, dc, r = _sizes(m)
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    f, V, E, H = m["d_ff"], m["vocab_size"], m["num_experts"], m["experts_held"]
    out = {"embed/embedding": ((V, d), False, weights.EMBED_STD),
           "embed/unembed": ((d, V), False, d ** -0.5),
           "final_norm": ((d,), False, weights.NORM_STD)}
    for i, (mixer, ffn) in enumerate(m["layers"]):
        b = f"blocks/{i}/"
        out[b + "norm1"] = ((d,), True, weights.NORM_STD)
        if mixer == "mamba":
            out |= {b + "mamba/in_proj": ((d, 2 * di), True, d ** -0.5),
                    b + "mamba/conv_w": ((dc, di), True, (3 * dc) ** -0.5),
                    b + "mamba/conv_b": ((di,), True, CONV_BIAS_STD),
                    b + "mamba/x_proj": ((di, r + 2 * n), True, di ** -0.5),
                    b + "mamba/dt_proj": ((r, di), True, r ** -0.5),
                    b + "mamba/dt_bias": ((di,), True, DT_BIAS_STD),
                    b + "mamba/A_log": ((di, n), True, A_LOG_STD),
                    b + "mamba/D": ((di,), True, D_STD),
                    b + "mamba/out_proj": ((di, d), True, di ** -0.5),
                    b + "mamba/dt_norm": ((r,), True, weights.NORM_STD),
                    b + "mamba/B_norm": ((n,), True, weights.NORM_STD),
                    b + "mamba/C_norm": ((n,), True, weights.NORM_STD)}
        elif mixer == "attn":
            out |= {b + "attn/wq": ((d, q), True, d ** -0.5),
                    b + "attn/wk": ((d, kv), True, d ** -0.5),
                    b + "attn/wv": ((d, kv), True, d ** -0.5),
                    b + "attn/wo": ((q, d), True, q ** -0.5)}
        else:
            raise ValueError(f"jamba family: no mixer {mixer!r}")
        out[b + "norm2"] = ((d,), True, weights.NORM_STD)
        if ffn == "mlp":
            out |= {b + "mlp/wi": ((d, f), True, d ** -0.5),
                    b + "mlp/wg": ((d, f), True, d ** -0.5),
                    b + "mlp/wo": ((f, d), True, f ** -0.5)}
        elif ffn == "moe":
            out |= {b + "moe/router": ((d, E), True, d ** -0.5),
                    b + "moe/wi": ((H, d, f), True, d ** -0.5),
                    b + "moe/wg": ((H, d, f), True, d ** -0.5),
                    b + "moe/wo": ((H, f, d), True, f ** -0.5)}
        else:
            raise ValueError(f"jamba family: no FFN {ffn!r}")
    return out


# -- the reference ------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 3))
def position_weights(mf, key, superblock, i: int) -> dict:
    """Superblock position ``i``'s leaves of superblock ``superblock``,
    keyed by their paths less ``blocks/<i>/``: the experts in the served
    dtype, every other leaf rounded to it and held in float32."""
    m = dict(mf)
    prefix, out = f"blocks/{i}/", {}
    for path, (shape, _, std) in layout(m).items():
        if path.startswith(prefix):
            x = weights.draw(jax.random.fold_in(weights._leaf_key(key, path), superblock),
                             shape, std)
            name = path.removeprefix(prefix)
            out[name] = (x.astype(jnp.dtype(m["dtype"])) if name in EXPERT_LEAVES
                         else weights._rounded(x, m["dtype"]))
    return out


@partial(jax.jit, static_argnums=(2, 3))
def mamba(xs, w, mf, fp8):
    """xs: (T, d) float32 -> the same after the Mamba sub-block."""
    m = dict(mf)
    _, di, n, dc, r = _sizes(m)
    T, eps = xs.shape[0], m["norm_eps"]
    u, z = jnp.split(mm(rms(xs, w["norm1"], eps), w["mamba/in_proj"], fp8), 2, axis=-1)
    pad = jnp.pad(u, ((dc - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(pad[j:j + T] * w["mamba/conv_w"][j] for j in range(dc))
                    + w["mamba/conv_b"])
    dt, B, C = jnp.split(mm(u, w["mamba/x_proj"], fp8), [r, r + n], axis=-1)
    dt = rms(dt, w["mamba/dt_norm"], eps)
    B, C = rms(B, w["mamba/B_norm"], eps), rms(C, w["mamba/C_norm"], eps)
    dt = jax.nn.softplus(mm(dt, w["mamba/dt_proj"], fp8) + w["mamba/dt_bias"])
    A = -jnp.exp(w["mamba/A_log"])

    def step(h, t):
        dt_t, u_t, B_t, C_t = t
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * u_t)[:, None] * B_t[None, :]
        return h, jnp.dot(h, C_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32), (dt, u, B, C))
    y = (y + u * w["mamba/D"]) * jax.nn.silu(z)
    return xs + mm(y, w["mamba/out_proj"], fp8)


@partial(jax.jit, static_argnums=(2, 3))
def attn(xs, w, mf, fp8):
    """Causal GQA attention without positional encoding."""
    m = dict(mf)
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    T = xs.shape[0]
    h = rms(xs, w["norm1"], m["norm_eps"])
    q = mm(h, w["attn/wq"], fp8).reshape(T, H, hd)
    k = mm(h, w["attn/wk"], fp8).reshape(T, K, hd)
    v = mm(h, w["attn/wv"], fp8).reshape(T, K, hd)
    return xs + mm(attention(q, k, v).reshape(T, H * hd), w["attn/wo"], fp8)


def _swiglu(h, wi, wg, wo, fp8):
    return mm(jax.nn.silu(mm(h, wg, fp8)) * mm(h, wi, fp8), wo, fp8)


@partial(jax.jit, static_argnums=(2, 3))
def mlp(xs, w, mf, fp8):
    h = rms(xs, w["norm2"], dict(mf)["norm_eps"])
    return xs + _swiglu(h, w["mlp/wi"], w["mlp/wg"], w["mlp/wo"], fp8)


@partial(jax.jit, static_argnums=(2, 3))
def moe(xs, w, mf, fp8):
    """The held experts' part of the expert layer, summed densely over the
    held experts, each widened to float32 in its turn."""
    m = dict(mf)
    h = rms(xs, w["norm2"], m["norm_eps"])
    probs = jax.nn.softmax(mm(h, w["moe/router"], fp8), axis=-1)
    gates, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    if m["moe_renormalize"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    def expert(e, acc):
        mine = jnp.sum(jnp.where(idx == m["expert_offset"] + e, gates, 0.0), axis=-1)
        wi, wg, wo = (jax.lax.dynamic_index_in_dim(w[k], e, keepdims=False).astype(jnp.float32)
                      for k in EXPERT_LEAVES)
        return acc + mine[:, None] * _swiglu(h, wi, wg, wo, fp8)

    return jax.lax.fori_loop(0, m["experts_held"], expert, xs)


MIXERS = {"mamba": mamba, "attn": attn}
FFNS = {"mlp": mlp, "moe": moe}


def hidden(m: dict, seed: int, rows: list, top: dict, fp8: bool) -> list:
    """Final hidden states of token rows, one superblock position at a
    time: each position's weights are drawn once, after the previous
    position's outputs (so that even inside one traced program they are
    never all held at once), and applied to every row."""
    mf, key = weights._frozen(m), weights.seed_key(seed)
    xs = [jnp.take(top["embed/embedding"], jnp.asarray(t), axis=0) for t in rows]
    for s in range(m["num_superblocks"]):
        for i, (mixer, ffn) in enumerate(m["layers"]):
            at, xs = jax.lax.optimization_barrier((jnp.uint32(s), xs))
            w = position_weights(mf, key, at, i)
            xs = [FFNS[ffn](MIXERS[mixer](x, w, mf, fp8), w, mf, fp8) for x in xs]
            del w
    return xs


# -- the arithmetic -----------------------------------------------------------


def _dtype_bytes(m: dict) -> int:
    return jnp.dtype(m["dtype"]).itemsize


def _elements(m: dict, keep) -> int:
    """Elements of the layout's leaves whose path ``keep`` accepts, over
    every superblock."""
    n = m["num_superblocks"]
    return sum(int(np.prod(s)) * (n if st else 1) for p, (s, st, _) in layout(m).items()
               if keep(p))


def _is_expert(path: str) -> bool:
    return path.endswith(EXPERT_LEAVES)


def _matmul(path: str) -> bool:
    """Leaves that enter a matmul once per token: not the embedding table,
    the depthwise conv, the scan's parameters, biases or norms."""
    return (path.split("/")[-1] in ("in_proj", "x_proj", "dt_proj", "out_proj", "wq", "wk",
                                    "wv", "wo", "wi", "wg", "router", "unembed")
            and not _is_expert(path))


def _expert_elements(m: dict) -> int:
    """One expert's elements (wi, wg, wo)."""
    return 3 * m["d_model"] * m["d_ff"]


def _layer_counts(m: dict):
    kinds = [k for layer in m["layers"] for k in layer]
    n = m["num_superblocks"]
    return n * kinds.count("mamba"), n * kinds.count("attn"), n * kinds.count("moe")


def _per_token(m: dict) -> float:
    """FLOPs of one token outside the experts and attention's query-key
    pairs: every matmul weight twice, the conv and the scan of each Mamba
    layer (7 operations per state element: dt A, the input's outer
    product, the decay and its add, and C's product and sum)."""
    _, di, n, dc, _ = _sizes(m)
    mamba_layers, _, _ = _layer_counts(m)
    return 2.0 * _elements(m, _matmul) + mamba_layers * (2 * dc * di + 7 * di * n)


def _pair_flops(m: dict) -> float:
    """QK^T and PV of one query-key pair, over heads and attention layers."""
    return 4.0 * m["num_heads"] * m["head_dim"] * _layer_counts(m)[1]


def _routed(counters: dict) -> np.ndarray:
    """(expert layers, experts held) pairs routed in the tick's decode step."""
    return np.asarray(counters["expert_tokens"])


def prefill_flops(m: dict, L: int) -> float:
    held_per_token = m["num_experts_per_tok"] * m["experts_held"] / m["num_experts"]
    experts = _layer_counts(m)[2] * held_per_token * 2.0 * _expert_elements(m)
    return L * (_per_token(m) + experts) + _pair_flops(m) * L * (L + 1) / 2


def decode_flops(m: dict, kv_len: int, counters: dict) -> float:
    pairs = int(_routed(counters).sum())
    return _per_token(m) + pairs * 2.0 * _expert_elements(m) + _pair_flops(m) * kv_len


def decode_bytes(m: dict, kv_len: int, counters: dict) -> float:
    """Every weight but the embedding table (one row of it) and the
    experts, each held expert that the step hit once, the valid keys and
    values, and each Mamba layer's state read and written (``h`` in
    float32, the conv's window in the served dtype)."""
    d, di, n, dc, _ = _sizes(m)
    b = _dtype_bytes(m)
    mamba_layers, attn_layers, _ = _layer_counts(m)
    rest = _elements(m, lambda p: p != "embed/embedding" and not _is_expert(p)) + d
    hit = int((_routed(counters) > 0).sum())
    kv = kv_len * 2 * m["num_kv_heads"] * m["head_dim"] * attn_layers
    state = mamba_layers * 2 * (4 * di * n + b * (dc - 1) * di)
    return float(b * (rest + hit * _expert_elements(m) + kv) + state)


def tick_counters(engine) -> dict:
    """The decode's per-expert-layer counts, as the device array the
    engine keeps: nothing is copied to the host inside the window."""
    return {"expert_tokens": engine.expert_tokens}
