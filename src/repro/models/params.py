"""Parameter templates: single source of truth for shapes, init, and sharding.

Each model declares its parameters as a nested tree of ``TSpec`` leaves
(shape + logical sharding axes + init rule). From the same template we derive:

  * ``init_params``     — real arrays (deterministic per-path fold_in keys)
  * ``abstract_params`` — ShapeDtypeStructs (dry-run: no allocation)
  * ``param_axes``      — logical axis tree (-> NamedShardings via rules)
  * ``count_params``    — exact parameter count

Stacked (scanned) layers wrap a per-layer template with ``stack`` which
prepends the superblock-count dimension.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TSpec",
    "stack",
    "init_params",
    "abstract_params",
    "param_axes",
    "count_params",
    "tree_bytes",
]


@dataclass(frozen=True)
class TSpec:
    """One parameter leaf."""

    shape: tuple[int, ...]
    axes: tuple  # logical axis names (len == ndim), None = replicated
    init: str = "normal"  # "normal" | "zeros" | "ones" | "fan_in"
    std: float = 0.02
    dtype: str | None = None  # override model dtype

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")


def stack(template: Any, n: int) -> Any:
    """Prepend a stacked-layer dim of size n to every leaf (scan over layers)."""

    def f(leaf: TSpec) -> TSpec:
        return replace(leaf, shape=(n, *leaf.shape), axes=(None, *leaf.axes))

    return jax.tree.map(f, template, is_leaf=lambda x: isinstance(x, TSpec))


def _is_tspec(x) -> bool:
    return isinstance(x, TSpec)


def _path_key(path) -> int:
    s = jax.tree_util.keystr(path)
    return int.from_bytes(hashlib.sha256(s.encode()).digest()[:4], "little")


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw_normal(key, std, shape, dtype):
    """``(normal(key, shape, f32) * std).astype(dtype)`` as one program: the
    scaled float32 copy never lands on the device next to the resident
    leaves, which a published-width MLP stack (1.1 G elements) cannot afford.
    The barrier keeps the draw unfused, so the values are bit-identical to
    the eager expression."""
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape, jnp.float32))
    return (z * std).astype(dtype)


def init_params(template: Any, key: jax.Array, dtype: jnp.dtype) -> Any:
    """Materialise arrays. Per-leaf keys are fold_in(key, hash(path)):
    deterministic, order-independent, stable across refactors."""

    def f(path, leaf: TSpec):
        d = jnp.dtype(leaf.dtype) if leaf.dtype else dtype
        k = jax.random.fold_in(key, _path_key(path))
        if leaf.init == "zeros":
            return jnp.zeros(leaf.shape, d)
        if leaf.init == "ones":
            return jnp.ones(leaf.shape, d)
        if leaf.init == "fan_in":
            fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
            return _draw_normal(k, 1.0 / np.sqrt(fan_in), leaf.shape, d)
        if leaf.init == "normal":
            return _draw_normal(k, leaf.std, leaf.shape, d)
        raise ValueError(leaf.init)

    return jax.tree_util.tree_map_with_path(f, template, is_leaf=_is_tspec)


def abstract_params(template: Any, dtype: jnp.dtype) -> Any:
    def f(leaf: TSpec):
        d = jnp.dtype(leaf.dtype) if leaf.dtype else dtype
        return jax.ShapeDtypeStruct(leaf.shape, d)

    return jax.tree.map(f, template, is_leaf=_is_tspec)


def param_axes(template: Any) -> Any:
    return jax.tree.map(lambda l: tuple(l.axes), template, is_leaf=_is_tspec)


def is_axes_leaf(x) -> bool:
    """Leaf predicate for logical-axes trees: a tuple of axis names/None.

    Distinguishes axes tuples from structural tuples (e.g. the per-position
    superblock tuple, whose elements are dicts)."""
    return isinstance(x, tuple) and all(e is None or isinstance(e, str) for e in x)


def count_params(template: Any) -> int:
    leaves = jax.tree.leaves(template, is_leaf=_is_tspec)
    return int(sum(np.prod(l.shape) for l in leaves))


def tree_bytes(tree: Any) -> int:
    """Total bytes of a tree of arrays / ShapeDtypeStructs."""
    return int(
        sum(np.prod(x.shape) * jnp.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))
    )
