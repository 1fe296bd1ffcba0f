"""Logical-axis sharding rules (MaxText-style) for the model zoo.

Model code annotates activations/params with *logical* axis names; the rules
table maps them to mesh axes.  DP over ("pod", "data"); TP/EP/CP over
"model".  When no mesh is active the constraint is a no-op so smoke tests on
one CPU device run unmodified.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "LOGICAL_RULES",
    "logical_to_spec",
    "shard",
    "param_spec",
    "activation_rules",
    "use_mesh",
    "current_mesh",
]


# logical axis -> mesh axes (None = replicated).  ("pod","data") only ever
# shards batch-like axes; "model" shards head/ffn/expert/vocab axes.
LOGICAL_RULES: Tuple[Tuple[str, Optional[object]], ...] = (
    ("batch", ("pod", "data")),
    ("seq", None),                  # sequence kept whole for training
    ("cp_seq", "model"),            # context-parallel KV cache sequence
    ("embed", None),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("head_dim", None),
    ("ffn", "model"),
    ("moe_ffn", None),              # EP owns "model"; per-expert FFN unsharded
    ("experts", "model"),           # expert parallelism
    ("vocab", "model"),
    ("kv_lora", None),
    ("ssm_heads", "model"),
    ("ssm_state", None),
    ("lru_width", "model"),
    ("conv_dim", "model"),
    ("group", None),
    ("capacity", None),
    ("fsdp_embed", ("pod", "data")),  # ZeRO/FSDP param sharding for huge archs
)

_state = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def _rules():
    return dict(getattr(_state, "rules", None) or LOGICAL_RULES)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[Sequence] = None):
    """Activate a mesh (and optional rule overrides) for model tracing."""
    prev_mesh = getattr(_state, "mesh", None)
    prev_rules = getattr(_state, "rules", None)
    _state.mesh = mesh
    _state.rules = tuple(rules) if rules is not None else None
    try:
        yield
    finally:
        _state.mesh = prev_mesh
        _state.rules = prev_rules


def logical_to_spec(logical_axes: Sequence[Optional[str]]) -> P:
    """Map logical axis names to a PartitionSpec under the active rules."""
    mesh = current_mesh()
    rules = _rules()
    axes = []
    for name in logical_axes:
        if name is None:
            axes.append(None)
            continue
        target = rules.get(name)
        if target is None or mesh is None:
            axes.append(None)
            continue
        # Drop mesh axes that don't exist on this mesh (e.g. "pod" on the
        # single-pod mesh).
        if isinstance(target, tuple):
            present = tuple(a for a in target if a in mesh.axis_names)
            axes.append(present if present else None)
        else:
            axes.append(target if target in mesh.axis_names else None)
    return P(*axes)


def shard(x, *logical_axes):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_spec(*logical_axes) -> P:
    """PartitionSpec for a parameter tensor (used to build in_shardings)."""
    return logical_to_spec(logical_axes)
