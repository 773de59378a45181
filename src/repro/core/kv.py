"""KV-cache reorganization (paper §3.2): apply re-root MovePlans and
verification compaction to model caches while preserving the
``[prefix | tree]`` layout invariant.

All moves are gather-then-scatter on the functional cache (sources are read
from the pre-move cache in full before any write), so overlapping src/dst
rows are safe by construction.  Row ops touch only attention-cache leaves
("k"/"v"/"ckv"/"krope"); SSM states and cross-encoder KV are structurally
exempt (chain mode / static).

The row moves dispatch through ``repro.kernels.ops.kv_move_rows``: an
index-based reference path (gather/scatter of exactly the M plan rows), or —
under ``flags.use_pallas_kv_moves`` — the fused Pallas kernel that DMAs only
the moved rows, O(B·M·F) HBM traffic instead of the two dense O(B·S·F)
passes of the retired one-hot einsum formulation (docs/kernels.md).

Speculative fork / rollback contract (the round's lookahead): because every
operation here is functional, a cache "snapshot" is just a retained
reference — zero copies.  The lookahead (``EngineSession.draft_next_tree``) keeps the
pre-reroot (tree, dcache) pair alive and re-roots through a NON-donating jit;
if the lookahead seed is rejected, ``reconcile`` simply re-applies the move
plan to the retained reference (exact rollback), and if it commits, dropping
the reference frees the fork.  Any new cache op must preserve this: never
mutate a cache in place, and never donate a buffer the caller may still hold.
``apply_moves(..., donate=True)`` is the one sanctioned exception — it tells
the fused kernel it may alias output onto input, and is only legal inside a
jit that donates the cache argument (the caller provably holds no reference).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.kernels import ops
from repro.models.attention import KV_AXES
from repro.sharding import spec_for

ROW_KEYS = ("k", "v", "ckv", "krope")


def leaf_axes(key, ndim: int) -> tuple:
    """Logical axes of one stacked cache leaf [U, B, ...] named ``key``:
    attention K/V [U, B, S, Hkv, hd] as in the model's own constraints, other
    row leaves (MLA latents) by sequence, everything else replicated."""
    if key in ("k", "v") and ndim == 5:
        return ("layers",) + KV_AXES
    if key in ROW_KEYS:
        return ("layers", "cache_batch", "kv_seq") + (None,) * (ndim - 3)
    return (None,) * ndim


def _key_of(path):
    keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
    return keys[-1] if keys else None


def cache_shardings(mesh, cache, rules=None):
    """NamedSharding tree for ``cache`` (arrays or shape structs) on ``mesh``:
    where ``init_state`` places the serving caches, in the layout the jitted
    round programs keep them in."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: NamedSharding(
            mesh, spec_for(mesh, leaf_axes(_key_of(path), x.ndim), x.shape, rules)),
        cache)


def map_row_leaves(cache, fn):
    """Apply ``fn(leaf, key)`` to every row-indexed cache leaf [U, B, S, ...]."""

    def rec(x):
        if isinstance(x, dict):
            return {k: (fn(v, k) if k in ROW_KEYS else rec(v)) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            return type(x)(rec(v) for v in x)
        return x

    return {"len": cache["len"], "groups": rec(cache["groups"])}


def apply_moves(cache, src, dst, mask, *, donate: bool = False):
    """src/dst/mask: [B, M] row move plan, applied to every row leaf.

    ``donate=True`` permits in-place movement (fused kernel aliasing) and is
    only legal when the wrapping jit donates the cache — see the module
    docstring's rollback contract.
    """

    def per_leaf(arr, key):  # [U, B, S, ...]
        return ops.kv_move_rows(arr, src, dst, mask, donate=donate,
                                axes=leaf_axes(key, arr.ndim))

    return map_row_leaves(cache, per_leaf)


def set_length(cache, new_len):
    return {**cache, "len": jnp.asarray(new_len, jnp.int32)}


# -----------------------------------------------------------------------------
# per-slot (batch-row) lifecycle — continuous-batching serving (serving/)
# -----------------------------------------------------------------------------
# A "slot" is one batch row of a long-lived serving cache.  Requests are
# admitted into free slots (install_slot: copy a fresh single-request prefill
# cache into the row) and retired (zero_slot: physically clear the row so no
# KV can leak into the slot's next occupant).  Both touch EVERY array leaf —
# attention K/V rows and recurrent states alike — and leave the global "len"
# scalar alone: per-slot length bookkeeping lives in the per-row tree
# (tree.plen); spec_forward masks are explicit and never read "len".
#
# Both run as ONE stacked update per call: under ``use_pallas_kv_moves`` a
# single ``slot_write_rows`` launch DMAs one row per leaf (zeroing uses an
# all-zeros donor cache), otherwise the XLA fallback below issues the
# per-leaf updates inside one jitted program.  Leaves that don't fit the
# kernel contract fall back per-call, so hybrid caches always work.


def _write_slot_rows(cache, donor, slot, fallback):
    """Shared install/zero body: write donor row 0 into ``slot`` of every
    groups leaf, fused when possible, else via ``fallback(big, one)``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(cache["groups"])
    big_leaves = [x for _, x in paths]
    one_leaves = jax.tree.leaves(donor["groups"])
    axes = [leaf_axes(_key_of(p), x.ndim) for p, x in paths]
    fused = ops.slot_write_rows(big_leaves, one_leaves, slot, axes)
    if fused is not None:
        return {"len": cache["len"], "groups": jax.tree.unflatten(treedef, fused)}
    return {"len": cache["len"],
            "groups": jax.tree.map(fallback, cache["groups"], donor["groups"])}


def install_slot(cache, src, slot):
    """Copy batch row 0 of single-request cache ``src`` into batch row
    ``slot`` of ``cache``.  ``slot`` may be traced (one jit for all slots)."""

    def copy(big, one):
        return big.at[:, slot].set(one[:, 0].astype(big.dtype))

    return _write_slot_rows(cache, src, slot, copy)


def zero_slot(cache, slot):
    """Zero batch row ``slot`` of every cache leaf (retired-slot hygiene:
    a recycled slot starts from provably clean state)."""
    zeros = {"groups": jax.tree.map(
        lambda x: jnp.zeros((x.shape[0], 1) + x.shape[2:], x.dtype), cache["groups"])}

    def clear(x, _z):
        return x.at[:, slot].set(jnp.zeros_like(x[:, 0]))

    return _write_slot_rows(cache, zeros, slot, clear)
