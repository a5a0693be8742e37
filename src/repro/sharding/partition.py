"""Logical-axis sharding: t5x-style rules mapping logical axis names to mesh axes.

Model code annotates parameters and activations with *logical* axis names
("batch", "heads", "ff", ...). Inside a ``use_rules`` context a
``ShardingRules`` resolves those to mesh axes and ``hint`` applies
``with_sharding_constraint``. Outside any context every hint is the
identity, so model code is mesh-agnostic. No served or measured path opens
a context yet: this is the seam a placement over several chips plugs into.

``ShardingRules.default`` is Megatron-style tensor parallelism over "model"
and batch data parallelism over ("pod", "data").
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "use_rules", "current_rules", "hint", "logical_to_spec", "named_sharding"]


@dataclass(frozen=True)
class ShardingRules:
    """Map logical axis name -> mesh axis (str | tuple | None)."""

    mesh: jax.sharding.Mesh
    rules: dict[str, object] = field(default_factory=dict)

    @staticmethod
    def default(mesh: jax.sharding.Mesh) -> "ShardingRules":
        has_pod = "pod" in mesh.axis_names
        batch_axes = ("pod", "data") if has_pod else ("data",)
        rules = {
            "batch": batch_axes,  # batch dim of activations / data
            "seq": None,  # residual-stream sequence dim
            "seq_inner": None,  # interior activations (heads/ff already use model)
            "kv_seq": None,  # key/value sequence dim (cache; see decode rules)
            "embed": None,  # d_model dim of activations & params
            "heads": "model",  # attention heads (param + activation)
            "qkv": "model",  # flattened heads*head_dim param dim
            "kv": "model",  # flattened kv_heads*head_dim param dim
            "ff": "model",  # MLP hidden
            "vocab": "model",  # embedding/logits vocab dim
            "expert": "model",  # MoE expert dim
            "expert_ff": "data",  # per-expert hidden (480B-class stacks must
            # shard over data too or they exceed per-device HBM)
            "mlstm_dk": "model",  # xLSTM matrix-memory key dim
            "cache_batch": batch_axes,  # KV cache batch dim
            "cache_kv": "model",  # KV cache flattened kv feature dim
            "cache_seq": None,  # KV cache sequence dim
            "conv_state": None,
        }
        return ShardingRules(mesh, rules)

    def spec(self, logical_axes: tuple) -> P:
        parts = []
        for ax in logical_axes:
            if ax is None:
                parts.append(None)
            else:
                parts.append(self.rules.get(ax))
        return P(*parts)


_local = threading.local()


@contextmanager
def use_rules(rules: ShardingRules | None):
    prev = getattr(_local, "rules", None)
    _local.rules = rules
    try:
        yield rules
    finally:
        _local.rules = prev


def current_rules() -> ShardingRules | None:
    return getattr(_local, "rules", None)


def logical_to_spec(logical_axes: tuple) -> P | None:
    rules = current_rules()
    if rules is None:
        return None
    return rules.spec(logical_axes)


def hint(x, *logical_axes):
    """with_sharding_constraint under the active rules; identity otherwise."""
    rules = current_rules()
    if rules is None:
        return x
    if x.ndim != len(logical_axes):
        raise ValueError(f"rank {x.ndim} vs logical axes {logical_axes}")
    spec = rules.spec(logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


def named_sharding(logical_axes: tuple) -> NamedSharding | None:
    rules = current_rules()
    if rules is None:
        return None
    return NamedSharding(rules.mesh, rules.spec(logical_axes))
