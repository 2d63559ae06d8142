"""Logical-axis sharding: params and activations carry *logical* axis names
(models/common.py); this module resolves them onto mesh axes per a rule set.

Resolution is best-effort: a logical axis whose dimension is not divisible by
the product of its mesh axes is dropped (replicated) rather than erroring —
the divisibility fallback that lets e.g. a 24-head model run on a 16-wide
tensor axis (the weight stays FSDP-sharded on `embed`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (str), tuple of mesh axes, or None (replicated)
PARAM_RULES: dict[str, Any] = {
    "batch": None, "seq": None,
    "embed": "data",          # FSDP/ZeRO-3: weights + opt state sharded on data
    "ffn": "model",           # TP
    "heads": "model",         # TP
    "kv_heads": None,         # GQA kv groups are narrower than the TP axis
    "head_dim": None,
    "vocab": "model",         # TP on embedding/lm_head
    "experts": "model",       # EP
    "layers": None,           # scan axis
    "state": None, "capacity": None, "kv_lora": None, "q_lora": None,
    "conv": None, "frames": None, "experts_group": None, "attn_seq": None,
}

# activation rules (training / prefill): batch data-parallel over pod+data
ACT_RULES: dict[str, Any] = {
    **{k: None for k in PARAM_RULES},
    "batch": ("pod", "data"),
    "heads": "model", "ffn": "model", "vocab": "model", "experts": "model",
    "embed": None, "kv_seq": None,
    "experts_group": ("pod", "data"),  # grouped MoE dispatch locality
    "attn_seq": None,                  # optional SP for unshardable heads
}

# activation rules for long-context decode (batch too small to shard):
# sequence-parallel KV cache over the data axis.
ACT_RULES_SP: dict[str, Any] = {
    **ACT_RULES,
    "batch": None,
    "kv_seq": "data",
    "seq": None,
}


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """A device mesh whose axes are all Auto.  The models place activations
    with sharding hints (:func:`logical_constraint`) and leave the rest to
    the partitioner, which only Auto axes allow: on JAX's default Explicit
    axes every op's sharding is part of its type, and a gather or an add of
    differently sharded operands is a type error."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@dataclasses.dataclass
class ShardingCtx:
    mesh: Mesh
    param_rules: dict[str, Any]
    act_rules: dict[str, Any]


_STATE = threading.local()


def current_ctx() -> ShardingCtx | None:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, param_rules=None, act_rules=None):
    prev = current_ctx()
    _STATE.ctx = ShardingCtx(
        mesh=mesh,
        param_rules=dict(param_rules or PARAM_RULES),
        act_rules=dict(act_rules or ACT_RULES),
    )
    try:
        with mesh:
            yield _STATE.ctx
    finally:
        _STATE.ctx = prev


def _mesh_axes_for(logical: str, rules: dict, mesh: Mesh):
    mapped = rules.get(logical)
    if mapped is None:
        return None
    axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
    axes = tuple(a for a in axes if a in mesh.shape)
    return axes or None


def resolve_spec(logical_axes: tuple, rules: dict, mesh: Mesh,
                 shape: tuple | None = None) -> P:
    """Logical axes tuple -> PartitionSpec, with divisibility fallback."""
    used: set[str] = set()
    parts = []
    for d, name in enumerate(logical_axes):
        if name is None:
            parts.append(None)
            continue
        axes = _mesh_axes_for(name, rules, mesh)
        if axes is None:
            parts.append(None)
            continue
        axes = tuple(a for a in axes if a not in used)
        if not axes:
            parts.append(None)
            continue
        if shape is not None:
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if shape[d] % size != 0:
                parts.append(None)  # divisibility fallback: replicate
                continue
        used.update(axes)
        parts.append(axes if len(axes) > 1 else axes[0])
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def param_sharding(spec_tree, params, mesh: Mesh | None = None,
                   rules: dict | None = None):
    """Param logical-spec tree -> NamedSharding tree (shape-aware)."""
    ctx = current_ctx()
    mesh = mesh or (ctx.mesh if ctx else None)
    rules = rules or (ctx.param_rules if ctx else PARAM_RULES)
    if mesh is None:
        raise ValueError("no mesh: call inside use_sharding() or pass mesh=")

    def one(spec, p):
        return NamedSharding(mesh, resolve_spec(spec, rules, mesh, p.shape))

    return jax.tree.map(
        one, spec_tree, params, is_leaf=lambda s: isinstance(s, tuple)
    )


def logical_constraint(x, *logical_axes):
    """with_sharding_constraint by logical names; identity outside a mesh."""
    ctx = current_ctx()
    if ctx is None:
        return x
    spec = resolve_spec(tuple(logical_axes), ctx.act_rules, ctx.mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))
