"""Seeded weights: one generator for the served weights and the reference.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed_key,
crc32(path)), layer)``, as a uniform draw with the leaf's standard
deviation. The draw is built from raw bits with a single rounding step
(one multiply, then the cast to the served dtype), so the served weights
(all layers at once, in one jitted call) and the reference's (one layer at
a time, in float32) hold the same numbers bit for bit however XLA fuses
the two programs.

The layout below names the leaves of the served model's parameter tree.
It is written from the configuration file alone; the harness checks it
against the program's own abstract tree before anything runs.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1  # norm scales enter as (1 + scale)
EMBED_STD = 1.0


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed: the low 32 bits seed it, the rest fold in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def layout(m: dict) -> dict[str, tuple[tuple[int, ...], bool, float]]:
    """path -> (shape of one layer's leaf, stacked over layers, std)."""
    d, H, K, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    f, V = m["d_ff"], m["vocab_size"]
    q, kv = H * hd, K * hd
    out = {
        "embed/embedding": ((V, d), False, EMBED_STD),
        "embed/unembed": ((d, V), False, d ** -0.5),
        "final_norm": ((d,), False, NORM_STD),
        "blocks/norm1": ((d,), True, NORM_STD),
        "blocks/attn/wq": ((d, q), True, d ** -0.5),
        "blocks/attn/wk": ((d, kv), True, d ** -0.5),
        "blocks/attn/wv": ((d, kv), True, d ** -0.5),
        "blocks/attn/wo": ((q, d), True, q ** -0.5),
        "blocks/norm2": ((d,), True, NORM_STD),
        "blocks/mlp/wi": ((d, f), True, d ** -0.5),
        "blocks/mlp/wo": ((f, d), True, f ** -0.5),
    }
    if m["gated_mlp"]:
        out["blocks/mlp/wg"] = ((d, f), True, d ** -0.5)
    return out


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(key, shape, std: float) -> jax.Array:
    """Uniform on [-sqrt(3) std, sqrt(3) std) in float32, from raw bits."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = jax.lax.bitcast_convert_type((bits >> 9) | np.uint32(0x3F800000), jnp.float32)
    centred = one_two - jnp.float32(1.5)  # exact: [-0.5, 0.5)
    return centred * jnp.float32(2.0 * np.sqrt(3.0) * std)


def served_jit(m: dict):
    """A jitted ``key -> parameter tree`` in the served dtype: all leaves in
    one program on the device."""
    lay = layout(m)
    n = m["num_superblocks"]
    dtype = jnp.dtype(m["dtype"])

    def build(key):
        leaves = {}
        for path, (shape, stacked, std) in lay.items():
            k = _leaf_key(key, path)
            if stacked:
                one = lambda l, k=k, shape=shape, std=std: draw(
                    jax.random.fold_in(k, l), shape, std).astype(dtype)
                leaves[path] = jax.vmap(one)(jnp.arange(n, dtype=jnp.uint32))
            else:
                leaves[path] = draw(k, shape, std).astype(dtype)
        return nest(leaves)

    return jax.jit(build)


def served_params(m: dict, seed: int):
    return served_jit(m)(seed_key(seed))


def nest(leaves: dict):
    """Flat ``a/b/c`` paths -> the program's tree: a dict per level, and the
    stacked block leaves inside a one-element tuple (one layer kind)."""
    tree: dict = {}
    for path, leaf in leaves.items():
        parts = path.split("/")
        node = tree
        if parts[0] == "blocks":
            node = tree.setdefault("blocks", ({},))[0]
            parts = parts[1:]
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def layer_f32(m: dict, seed: int, layer: int) -> dict:
    """One layer's leaves, rounded to the served dtype, held in float32."""
    return _layer_f32(_frozen(m), seed_key(seed), jnp.uint32(layer))


def top_f32(m: dict, seed: int) -> dict:
    """The embedding, head and final norm, as :func:`layer_f32`."""
    return _top_f32(_frozen(m), seed_key(seed))


def _frozen(m: dict):
    return tuple(sorted((k, m[k]) for k in
                        ("d_model", "num_heads", "num_kv_heads", "head_dim",
                         "d_ff", "vocab_size", "gated_mlp", "dtype")))


def _rounded(x, dtype):
    return x.astype(jnp.dtype(dtype)).astype(jnp.float32)


def _layer_impl(mf, key, layer):
    m = dict(mf)
    out = {}
    for path, (shape, stacked, std) in layout(m).items():
        if stacked:
            k = jax.random.fold_in(_leaf_key(key, path), layer)
            out[path.removeprefix("blocks/")] = _rounded(draw(k, shape, std), m["dtype"])
    return out


def _top_impl(mf, key):
    m = dict(mf)
    return {path: _rounded(draw(_leaf_key(key, path), shape, std), m["dtype"])
            for path, (shape, stacked, std) in layout(m).items() if not stacked}


_layer_f32 = jax.jit(_layer_impl, static_argnums=0)
_top_f32 = jax.jit(_top_impl, static_argnums=0)
