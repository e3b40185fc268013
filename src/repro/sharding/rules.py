"""Logical-axis sharding rules.

Models annotate activations/params with *logical* axis names; a thread-local
rule set maps them to mesh axes. Outside a mesh context ``shard`` is a no-op,
so the same model code runs single-device smoke tests and 512-chip dry-runs.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# logical axis -> mesh axis (or tuple of mesh axes)
LOGICAL_RULES: Dict[str, object] = {
    "batch": ("pod", "data"),      # batch shards over pod x data
    "seq": None,
    "embed": None,                 # activation embed dim replicated
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",            # expert-parallel folded into model axis
    "vocab": "model",
    "cache_clients": "data",       # ACE cache client rows
    "cache_d": "model",            # ACE cache feature shards
    # parameter dims
    "p_embed": "data",             # FSDP: shard params' embed dim over data
    "p_vocab": "model",
    "p_mlp": "model",
    "p_heads": "model",
    "p_experts": "model",
    "p_expert_ff": None,
    "ssm_heads": "model",
    "ssm_inner": "model",
}


class use_rules:
    """Context manager activating a mesh + rule set for ``shard``."""

    def __init__(self, mesh: Optional[Mesh], rules: Optional[Dict] = None):
        self.mesh = mesh
        self.rules = dict(LOGICAL_RULES)
        if rules:
            self.rules.update(rules)

    def __enter__(self):
        self._prev = getattr(_state, "ctx", None)
        _state.ctx = (self.mesh, self.rules)
        return self

    def __exit__(self, *exc):
        _state.ctx = self._prev
        return False


def _active():
    return getattr(_state, "ctx", None)


def active_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing `use_rules` context, or None."""
    ctx = _active()
    return ctx[0] if ctx else None


def logical_to_spec(axes: Sequence[Optional[str]],
                    rules: Optional[Dict] = None,
                    mesh: Optional[Mesh] = None) -> P:
    ctx = _active()
    if rules is None:
        rules = ctx[1] if ctx else LOGICAL_RULES
    if mesh is None and ctx:
        mesh = ctx[0]
    mesh_axes = set(mesh.axis_names) if mesh is not None else None
    out = []
    for ax in axes:
        m = rules.get(ax) if ax else None
        if m is None:
            out.append(None)
            continue
        if isinstance(m, (tuple, list)):
            m = tuple(a for a in m if mesh_axes is None or a in mesh_axes)
            out.append(m if m else None)
        else:
            out.append(m if (mesh_axes is None or m in mesh_axes) else None)
    return P(*out)


def replicate(x: jax.Array) -> jax.Array:
    """Pin `x` fully replicated (explicit all-None constraint); no-op without
    an active mesh.

    Unlike `shard` — which *skips* the constraint when every axis maps to
    None, leaving the layout to GSPMD — this emits the constraint, cutting
    sharding propagation at `x`. The sharded scan engine pins client
    payloads with it: a raveled gradient is a concatenate of reshaped dot
    results, and letting a downstream 1-D model-axis constraint propagate
    back into that pattern miscompiles on the CPU SPMD partitioner
    (contraction partial sums replicated over the data axis get summed,
    scaling gradients by the replica count). Pinned payloads keep the client
    grad computation replicated — the point of the sharded scan is to shard
    the O(n·d) *server state*, not the client model."""
    ctx = _active()
    if ctx is None or ctx[0] is None:
        return x
    mesh = ctx[0]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * x.ndim))))


def shard(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Apply a logical sharding constraint; no-op without an active mesh."""
    ctx = _active()
    if ctx is None or ctx[0] is None:
        return x
    mesh, rules = ctx
    spec = logical_to_spec(axes, rules, mesh)
    if all(s is None for s in spec):
        return x  # fully-unconstrained: don't force replication
    # divisibility guard: drop constraints that do not divide
    fixed = []
    for dim, s in zip(x.shape, spec + (None,) * (x.ndim - len(spec))):
        if s is None:
            fixed.append(None)
            continue
        names = s if isinstance(s, tuple) else (s,)
        n = 1
        for a in names:
            n *= mesh.shape[a]
        fixed.append(s if dim % n == 0 else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


def param_spec_fn(path_logical_axes: Dict[str, Sequence[Optional[str]]],
                  mesh: Mesh):
    """Build a params-pytree -> NamedSharding pytree function (used by launch)."""
    def fn(logical_axes_tree):
        return jax.tree.map(
            lambda axes: NamedSharding(mesh, logical_to_spec(axes, mesh=mesh)),
            logical_axes_tree, is_leaf=lambda x: isinstance(x, (tuple, list)))
    return fn
