"""Logical-axis sharding: flax-linen-style rules without flax.

Models annotate tensors with *logical* axis names ("batch", "heads", "qrow",
…); a rules table maps logical names to mesh axes. ``constrain`` is a no-op
outside a rules context, so the same model code runs on 1 CPU device (smoke
tests) and on the 512-chip production mesh (dry-run).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


# Default production rules (single-pod). "pod" is prepended to batch for the
# multi-pod mesh. None = replicated along that logical axis.
DEFAULT_RULES: dict[str, tuple[str, ...] | None] = {
    "batch": ("data",),
    "seq": None,            # "model" under sequence parallelism (hillclimb)
    "kvseq": ("model",),    # decode KV cache length — the big decode tensor
    "embed": None,
    "heads": ("model",),
    "kv_heads": None,        # GQA kv heads are few; replicate
    "head_dim": None,
    "ffn": ("model",),
    "vocab": ("model",),
    "qrow": ("model",),     # Q-table rows = the "bank group" axis
    "rrow": None,            # R table = replicated LUT tier
    "experts": ("model",),  # EP
    "expert_ffn": None,
    "layers": None,
    "state": None,           # SSM state dim
    "mlp": None,
    "table": None,           # DLRM table index axis
}


def multi_pod_rules(rules: Mapping[str, tuple[str, ...] | None] | None = None) -> dict:
    """Extend batch-like axes over the 'pod' axis for the 2-pod mesh."""
    base = dict(DEFAULT_RULES if rules is None else rules)
    for k in ("batch",):
        v = base.get(k) or ()
        if "pod" not in v:
            base[k] = ("pod",) + tuple(v)
    return base


# Parameter (at-rest) rules: TP over `model`, FSDP over `data` — optimizer
# state inherits these leaf-for-leaf (ZeRO-style). Activations use
# DEFAULT_RULES; the two tables share logical names but map differently.
PARAM_RULES: dict[str, tuple[str, ...] | None] = {
    "batch": None,
    "embed": ("data",),      # FSDP axis for every weight's d_model dim
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": None,
    "ffn": ("model",),
    "vocab": ("model",),
    "qrow": ("model",),      # Q-table rows = the "bank group" axis
    "rrow": None,            # R table = replicated LUT tier
    "experts": ("model",),
    "expert_ffn": ("model",),  # picked up when `experts` doesn't divide
    "layers": None,
    "state": None,
    "mlp": ("model",),
    "table": None,
}


def multi_pod_param_rules(rules: Mapping | None = None) -> dict:
    """FSDP additionally over 'pod' for the 2-pod mesh."""
    base = dict(PARAM_RULES if rules is None else rules)
    v = base.get("embed") or ()
    if "pod" not in v:
        base["embed"] = ("pod",) + tuple(v)
    return base


def resolve_spec(
    mesh: Mesh,
    shape: Sequence[int],
    logical_axes: Sequence[str | None],
    rules: Mapping[str, tuple[str, ...] | None],
) -> P:
    """First-fit spec resolution with divisibility + duplicate-axis dropping.

    For each tensor dim, the rule's mesh axes are applied only if (a) the axis
    is not already used by an earlier dim of the same tensor and (b) the dim
    size is divisible by the product of the accepted axes. Handles kv=1 MQA,
    40 experts on a 16-way axis, odd vocab sizes, etc. with one rule table.
    """
    used: set[str] = set()
    parts: list = []
    for dim, ax in zip(shape, logical_axes):
        ent = rules.get(ax) if ax else None
        if not ent:
            parts.append(None)
            continue
        accepted: list[str] = []
        prod = 1
        for mesh_ax in ent:
            if mesh_ax in used or mesh_ax not in mesh.shape:
                continue
            size = mesh.shape[mesh_ax]
            if dim % (prod * size) == 0:
                accepted.append(mesh_ax)
                prod *= size
        used.update(accepted)
        if not accepted:
            parts.append(None)
        elif len(accepted) == 1:
            parts.append(accepted[0])
        else:
            parts.append(tuple(accepted))
    return P(*parts)


def _is_axes_tuple(a) -> bool:
    return isinstance(a, tuple) and all(isinstance(x, (str, type(None))) for x in a)


def shardings_for_tree(mesh: Mesh, tree, axes_tree, rules: Mapping):
    """NamedShardings for a pytree of arrays/SDS given its logical-axes tree.

    Leaves whose axes annotation is missing/mismatched fall back to
    replication — safe for scalars and small state.
    """
    flat_axes = {}

    def record(path, axes):
        flat_axes[path] = axes

    # walk axes tree by path so arrays and axes may differ in leaf typing
    for path, axes in jax.tree.flatten_with_path(
        axes_tree, is_leaf=_is_axes_tuple
    )[0]:
        record(tuple(str(p) for p in path), axes)

    def leaf(path, x):
        axes = flat_axes.get(tuple(str(p) for p in path))
        if not _is_axes_tuple(axes) or len(axes) != len(x.shape):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, resolve_spec(mesh, x.shape, axes, rules))

    flat, treedef = jax.tree.flatten_with_path(tree)
    return jax.tree.unflatten(treedef, [leaf(tuple(str(p) for p in pa), x) for pa, x in flat])


@contextlib.contextmanager
def use_rules(mesh: Mesh | None, rules: Mapping[str, tuple[str, ...] | None] | None):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, dict(rules) if rules else None)
    try:
        yield
    finally:
        _state.ctx = prev


def current_mesh() -> Mesh | None:
    ctx = getattr(_state, "ctx", None)
    return ctx[0] if ctx else None


def current_rules() -> dict | None:
    ctx = getattr(_state, "ctx", None)
    return ctx[1] if ctx else None


def spec_for(logical_axes: Sequence[str | None]) -> P:
    """PartitionSpec for a tuple of logical axis names under current rules.

    Mesh axes are assigned first-come-first-served across the tensor's dims
    (a mesh axis may appear at most once in a spec) — e.g. under sequence
    parallelism a (batch, seq, heads, head_dim) tensor gets seq->model and
    heads falls back to replicated."""
    rules = current_rules()
    if rules is None:
        return P()
    used: set[str] = set()
    parts = []
    for ax in logical_axes:
        ent = rules.get(ax) if ax else None
        ent = tuple(a for a in (ent or ()) if a not in used)
        used.update(ent)
        if not ent:
            parts.append(None)
        elif len(ent) == 1:
            parts.append(ent[0])
        else:
            parts.append(tuple(ent))
    return P(*parts)


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without rules/mesh."""
    mesh = current_mesh()
    rules = current_rules()
    if mesh is None or rules is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"{len(logical_axes)} axes for rank-{x.ndim} tensor")
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec_for(logical_axes)))


def named_sharding(mesh: Mesh, logical_axes: Sequence[str | None],
                   rules: Mapping | None = None) -> NamedSharding:
    """Resolve logical axes to a NamedSharding (for in_shardings at jit time)."""
    rules = dict(DEFAULT_RULES if rules is None else rules)
    parts = []
    for ax in logical_axes:
        ent = rules.get(ax) if ax else None
        if ent is None:
            parts.append(None)
        elif len(ent) == 1:
            parts.append(ent[0])
        else:
            parts.append(tuple(ent))
    return NamedSharding(mesh, P(*parts))


def tree_shardings(mesh: Mesh, axes_tree, rules: Mapping | None = None):
    """Map a pytree of logical-axes tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: named_sharding(mesh, axes, rules),
        axes_tree,
        is_leaf=lambda a: isinstance(a, tuple) and all(isinstance(x, (str, type(None))) for x in a),
    )
