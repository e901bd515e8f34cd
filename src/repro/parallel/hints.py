"""Logical activation-sharding hints.

Model code calls ``constrain(x, ("dp", None, "tp"))`` with *logical* axis
names; a context-scoped mapping translates them to mesh axes (or drops
them entirely when no mapping is active — the single-device CPU path).

Logical names:
  "dp"  — data-parallel batch axis (may be absent inside manual shard_map,
          where the batch is already device-local: map it to None there)
  "tp"  — tensor-parallel feature/head axis
  "ep"  — expert axis of MoE layers
  "sp"  — sequence axis (long-context cache sharding)
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _rules() -> Optional[dict]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def logical_axis_rules(rules: Optional[dict], mesh=None):
    """rules: {"tp": "model", "ep": "model", "dp": None, ...} or None.

    Pass ``mesh`` when the constrained code runs under plain jit (serving):
    with_sharding_constraint needs NamedSharding there, while inside
    shard_map the raw PartitionSpec binds to the context mesh."""
    prev = (_rules(), getattr(_state, "mesh", None))
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.rules, _state.mesh = prev


def active_mesh():
    """The mesh given to the active :func:`logical_axis_rules` (plain jit),
    or None."""
    return getattr(_state, "mesh", None)


def constrain(x: jax.Array, logical_spec) -> jax.Array:
    """Apply with_sharding_constraint if a rules mapping is active."""
    rules = _rules()
    if not rules:
        return x
    parts = []
    for name in logical_spec:
        if name is None:
            parts.append(None)
        else:
            parts.append(rules.get(name))
    # rank-adapt: align the spec to the trailing dims (a (B,S,F) hint
    # applied to a flattened (T,F) keeps its feature-axis meaning)
    if len(parts) > x.ndim:
        parts = parts[-x.ndim:]
    elif len(parts) < x.ndim:
        parts = [None] * (x.ndim - len(parts)) + parts
    if all(p is None for p in parts):
        return x
    mesh = active_mesh()
    if mesh is not None:
        from jax.sharding import NamedSharding
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*parts)))
    # inside a manual region: the bare spec resolves against the
    # context mesh
    return jax.lax.with_sharding_constraint(x, P(*parts))
