"""Logical-axis sharding rules and the mesh context.

Every parameter in the model zoo is created as a ``Param(value, axes)`` where
``axes`` names each dimension with a *logical* axis ("embed", "heads", "ff",
...).  ``spec_for`` maps logical axes onto mesh axes through a rules table,
falling back to replication whenever a dimension is not divisible by the mesh
axis it would shard over (this is what makes every config lower on every mesh
without per-arch special cases).

Mesh axes used throughout:
  "model" — tensor parallelism inside an ICI domain
  "data"  — FSDP parameter/optimizer sharding + batch data parallelism
  "pod"   — data parallelism across pods (DCN); params replicated per pod
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# -----------------------------------------------------------------------------
# Param: an array boxed with its logical axis names (single source of truth).
# -----------------------------------------------------------------------------


@dataclasses.dataclass
class Param:
    value: Any
    axes: tuple

    def __repr__(self):  # pragma: no cover - debugging aid
        shp = getattr(self.value, "shape", None)
        return f"Param(shape={shp}, axes={self.axes})"


def _param_flatten(p: Param):
    return (p.value,), p.axes


def _param_unflatten(axes, children):
    return Param(children[0], axes)


jax.tree_util.register_pytree_node(Param, _param_flatten, _param_unflatten)


def unbox(tree):
    """Param tree -> plain array tree."""
    return jax.tree.map(lambda p: p.value, tree, is_leaf=lambda x: isinstance(x, Param))


def axes_of(tree):
    """Param tree -> logical-axes tree (same structure as ``unbox``)."""
    return jax.tree.map(lambda p: p.axes, tree, is_leaf=lambda x: isinstance(x, Param))


def add_leading_axis(tree, name: str):
    """Prepend a logical axis to every Param (after vmapped/stacked init)."""
    return jax.tree.map(
        lambda p: Param(p.value, (name,) + tuple(p.axes)),
        tree,
        is_leaf=lambda x: isinstance(x, Param),
    )


# -----------------------------------------------------------------------------
# Logical -> mesh axis rules.
# -----------------------------------------------------------------------------

DEFAULT_RULES: dict[str, Any] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "act_seq": "model",  # sequence parallelism (flags.seq_shard_acts)
    "act_embed": None,
    # weights: FSDP ("data") on the large replicated dim, TP ("model") on the
    # split dim.  "pod" never shards weights (DCN all-gather too slow).
    "embed": "data",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "qk_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": None,  # experts replicated; ff-within-expert sharded (TP-in-expert)
    "experts_ep": "model",  # expert-parallel alternative (hillclimb)
    "inner": "model",  # ssm / rwkv inner dim
    "state": None,
    "conv": None,
    "lora": None,
    "unit": None,
    "layers": None,
    # caches
    "kv_seq": "model",  # decode-time KV cache sequence sharding
    "cache_batch": ("pod", "data"),
    None: None,
}

# Serving (SpecEngine on its 1-D "model" TP meshes): the KV cache shards over
# its kv heads, the layout tensor parallelism computes in, so attention and
# the row-move kernels run shard-local.  The sequence stays whole on each
# chip: a tree's rows move within one sequence, never across chips.
SERVING_RULES: dict[str, Any] = {**DEFAULT_RULES, "kv_seq": None}


def _axis_size(mesh: Mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh.shape.get(n, 1)
    return size


def spec_for(mesh: Mesh, axes, shape, rules=None) -> P:
    """Build a PartitionSpec for ``shape`` whose dims carry logical ``axes``.

    Falls back to replication per-dim when the mesh axis is absent or does not
    divide the dim.  Guarantees no mesh axis is used twice in one spec.
    """
    rules = rules or DEFAULT_RULES
    used: set[str] = set()
    out = []
    for dim, ax in zip(shape, axes):
        target = rules.get(ax)
        if target is None:
            out.append(None)
            continue
        names = (target,) if isinstance(target, str) else tuple(target)
        names = tuple(n for n in names if n in mesh.axis_names and n not in used)
        size = _axis_size(mesh, names)
        if not names or size == 1 or dim % size != 0:
            # partial fallback: try dropping trailing axes until divisible
            while names and (dim % _axis_size(mesh, names) != 0):
                names = names[:-1]
            if not names:
                out.append(None)
                continue
        used.update(names)
        out.append(names[0] if len(names) == 1 else names)
    return P(*out)


def sharding_for_tree(mesh: Mesh, params, rules=None):
    """Param tree -> NamedSharding tree (same structure as ``unbox``)."""

    def one(p: Param):
        shape = jax.eval_shape(lambda x: x, p.value).shape if not hasattr(p.value, "shape") else p.value.shape
        return NamedSharding(mesh, spec_for(mesh, p.axes, shape, rules))

    return jax.tree.map(one, params, is_leaf=lambda x: isinstance(x, Param))


# -----------------------------------------------------------------------------
# Mesh context: models call ``constrain`` freely; it is the identity when no
# mesh is active (single-device tests) and a sharding constraint otherwise.
# The context also carries the rules table (DEFAULT_RULES unless the caller
# enters with another, as the serving engine does with SERVING_RULES).
# -----------------------------------------------------------------------------

_CTX = threading.local()


def set_mesh(mesh: Mesh | None):
    _CTX.mesh = mesh


def get_mesh() -> Mesh | None:
    return getattr(_CTX, "mesh", None)


def get_rules() -> dict:
    return getattr(_CTX, "rules", None) or DEFAULT_RULES


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: dict | None = None):
    prev, prev_rules = get_mesh(), getattr(_CTX, "rules", None)
    set_mesh(mesh)
    _CTX.rules = rules
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        set_mesh(prev)
        _CTX.rules = prev_rules


def constrain(x, *axes, rules=None):
    """with_sharding_constraint under the active mesh; no-op without one."""
    mesh = get_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(mesh, axes, x.shape, rules or get_rules()))
    )


def shard_local(fn, args, axes, out_axes, *, local_dims):
    """Run ``fn`` on each device's shard of ``args`` under the active mesh.

    ``axes`` gives each argument's logical axes and ``out_axes`` those of the
    result (a list of them for several results); the active rules map them
    to mesh axes.  ``local_dims[i]`` names the dims of argument i along which
    ``fn`` is independent (heads, feature columns); the j-th entries of all
    arguments are the same logical dimension.  When every sharded dim of
    every argument is one of those, and the j-th dims of all arguments are
    sharded alike (query heads split only where kv heads split with them),
    ``fn`` runs per shard under ``jax.shard_map`` and no data moves.
    Otherwise every operand is replicated first (correct, at the cost of a
    gather), so a kernel with no partitioning rule never sees a shard it
    cannot handle.  Without a mesh, or on one device, ``fn`` runs as is.
    """
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    rules = get_rules()
    specs = [spec_for(mesh, ax, a.shape, rules) for a, ax in zip(args, axes)]
    local = all(
        all(s is None or d in dims for d, s in enumerate(tuple(spec)))
        for spec, dims in zip(specs, local_dims))
    for j in range(max(map(len, local_dims))):
        local &= len({tuple(spec)[dims[j]] for spec, dims in zip(specs, local_dims)
                      if j < len(dims)}) <= 1
    multi = isinstance(out_axes, list)
    outs = out_axes if multi else [out_axes]
    if local:
        shapes = jax.eval_shape(fn, *args)
        shapes = shapes if multi else [shapes]
        out_specs = tuple(spec_for(mesh, ax, o.shape, rules) for o, ax in zip(shapes, outs))
    else:
        specs = [P() for _ in args]
        out_specs = tuple(P() for _ in outs)
    return jax.shard_map(
        (lambda *a: tuple(fn(*a))) if multi else fn, mesh=mesh, in_specs=tuple(specs),
        out_specs=out_specs if multi else out_specs[0], check_vma=False,
    )(*args)
