"""Seeded weights: one generator for the served weights and the reference.

Every leaf is drawn from its own key, ``fold_in(fold_in(seed_key,
crc32(path)), layer)``, as a uniform draw with the leaf's standard
deviation. The draw is built from raw bits with a single rounding step
(one multiply, then the cast to the served dtype), so the served weights
(all layers at once, in one jitted call) and the reference's (one layer at
a time, in float32) hold the same numbers bit for bit however XLA fuses
the two programs.

The model family's layout names the leaves of the served model's parameter
tree. It is written from the configuration file alone; the harness checks
it against the program's own abstract tree before anything runs.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families

NORM_STD = 0.1  # norm scales enter as (1 + scale)
EMBED_STD = 1.0


def seed_key(seed: int) -> jax.Array:
    """A key from any whole seed: the low 32 bits seed it, the rest fold in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def layout(m: dict) -> dict[str, tuple[tuple[int, ...], bool, float]]:
    """path -> (shape of one superblock's leaf, stacked over superblocks,
    std): the model family's layout (``chipbench/families``)."""
    return families.of(m).layout(m)


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
    block leaves in a tuple with one dict per superblock position
    (``blocks/<i>/...``; a block path without a position is position 0)."""
    tree: dict = {}
    positions: dict[int, dict] = {}
    for path, leaf in leaves.items():
        parts = path.split("/")
        node = tree
        if parts[0] == "blocks":
            at = parts[1].isdigit()
            node = positions.setdefault(int(parts[1]) if at else 0, {})
            parts = parts[2 if at else 1:]
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    if positions:
        if sorted(positions) != list(range(len(positions))):
            raise ValueError(f"superblock positions {sorted(positions)} leave a gap")
        tree["blocks"] = tuple(positions[i] for i in range(len(positions)))
    return tree


def layer_f32(m: dict, seed: int, layer: int) -> dict:
    """One superblock's leaves, rounded to the served dtype, held in
    float32, keyed by their paths less ``blocks/``."""
    return _layer_f32(_frozen(m), seed_key(seed), jnp.uint32(layer))


def top_f32(m: dict, seed: int) -> dict:
    """The embedding, head and final norm, as :func:`layer_f32`."""
    return _top_f32(_frozen(m), seed_key(seed))


def _frozen(m: dict):
    """The model block as a hashable static argument."""
    return tuple(sorted((k, _hashable(v)) for k, v in m.items()))


def _hashable(v):
    return tuple(_hashable(x) for x in v) if isinstance(v, (list, tuple)) else v


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
