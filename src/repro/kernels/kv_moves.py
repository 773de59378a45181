"""Fused tree-aware KV-reorganization kernels (paper §3.2 + §3.3).

Every speculative round reorganizes the KV caches twice: the target cache
compacts the accepted tree rows into the prefix after verification, and the
draft cache re-roots onto the accepted path.  Both are row *moves* — M ≈ bs
rows out of an S_max-row cache — yet the XLA formulation (one-hot einsum
gather + scatter, models/attention.py) reads and rewrites the entire
[B, S, F] cache twice per layer stack, O(B·S·F) HBM traffic that grows with
context length instead of tree size.

``kv_move_rows_pallas`` replaces that with a single launch gridded over
(layer-stack U, batch B).  The cache stays a full-array HBM ref
(``memory_space=ANY``); the kernel DMAs the M source rows into a VMEM stage,
waits, then DMAs them back out to their destinations — a gather-all /
scatter-all barrier that gives parallel-assignment semantics for overlapping
src/dst windows (the compaction shift case) by construction.  HBM traffic is
O(B·M·F) touched rows.

Two variants, selected by ``donate``:

  donate=True   the output aliases the input (``input_output_aliases``); the
                move is in place.  Only safe when the caller owns the buffer
                (the jit wrapping it donates the cache argument).
  donate=False  the kernel first DMAs the whole (u, b) slab input→output and
                only then scatters the staged rows into the *output* — the
                input ref is never written.  This is the speculative
                lookahead variant: the async pipeline's rollback contract
                (kv.py docstring) keeps the pre-reroot cache alive as the
                reconcile fallback, so the re-root must not mutate it.

``slot_write_rows_pallas`` is the slot-lifecycle sibling: one launch that
DMAs batch row 0 of a donor cache into batch row ``slot`` of every serving
cache leaf (admission install, or retire-time zeroing via an all-zeros
donor), replacing the per-leaf ``.at[].set`` dispatch storm with a single
kernel whose cost is one cache row per leaf.

Index maps, aliasing rules, and the snapshot/no-donation contract for every
kernel in this package are catalogued in docs/kernels.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


# -----------------------------------------------------------------------------
# kv_move_rows — O(M) row moves on one [U, B, S, F] cache leaf
# -----------------------------------------------------------------------------


def _kv_move_kernel(src_ref, dst_ref, act_ref, cache_ref, out_ref,
                    stage, gsem, ssem, csem, *, copy_through: bool):
    """One (u, b) grid cell: move rows src[b, m] -> dst[b, m] where active.

    src_ref/dst_ref/act_ref are scalar-prefetch [B, M] i32; cache_ref/out_ref
    are full-array HBM refs [U, B, S, R, L] (out aliases cache when the caller
    donates).  One row is an [R, L] slab, so every DMA slices the untiled S
    axis (or the stage's leading axis) and never the tiled (R, L) pair — a
    one-row slice of a tiled dim is refused by Mosaic.  All gathers complete
    before any scatter starts, so an overlapping move plan behaves as a
    parallel assignment.
    """
    u, b = pl.program_id(0), pl.program_id(1)
    M = src_ref.shape[1]

    def gather(m):
        return pltpu.make_async_copy(
            cache_ref.at[u, b, pl.ds(src_ref[b, m], 1)],
            stage.at[pl.ds(m, 1)], gsem.at[m])

    def scatter(m):
        return pltpu.make_async_copy(
            stage.at[pl.ds(m, 1)],
            out_ref.at[u, b, pl.ds(dst_ref[b, m], 1)], ssem.at[m])

    if copy_through:
        # snapshot-preserving variant: land the untouched slab in the output
        # first; the staged rows then overwrite only their destinations there
        pltpu.make_async_copy(cache_ref.at[u, b], out_ref.at[u, b], csem).start()
    for m in range(M):

        @pl.when(act_ref[b, m] != 0)
        def _(m=m):
            gather(m).start()

    if copy_through:
        pltpu.make_async_copy(cache_ref.at[u, b], out_ref.at[u, b], csem).wait()
    for m in range(M):

        @pl.when(act_ref[b, m] != 0)
        def _(m=m):
            gather(m).wait()

    # barrier passed: every source row is staged in VMEM; writes may begin
    for m in range(M):

        @pl.when(act_ref[b, m] != 0)
        def _(m=m):
            scatter(m).start()

    for m in range(M):

        @pl.when(act_ref[b, m] != 0)
        def _(m=m):
            scatter(m).wait()


def kv_move_rows_pallas(arr, src, dst, active, *, donate: bool,
                        interpret: bool | None = None):
    """arr: [U, B, S, F]; src/dst/active: i32 [B, M] with active ∈ {0, 1}.

    Returns arr with rows moved (active: out[u, b, dst] = arr[u, b, src],
    parallel-assignment semantics).  ``donate=True`` aliases output to input
    (in-place; caller must own the buffer); ``donate=False`` never writes the
    input ref.  HBM traffic per (u, b): M·F gather + M·F scatter (+ one S·F
    pass-through copy for the non-donating variant).

    Inside the kernel a row is the [R, L] slab of a free [U, B, S, R, L]
    view (L = 128 lanes when F allows it), keeping S out of the tiled dims.
    """
    if arr.ndim != 4:
        raise ValueError(f"arr must be [U, B, S, F], got shape {arr.shape}")
    U, B, S, F = arr.shape
    L = 128 if F % 128 == 0 else F
    R = F // L
    M = src.shape[1]
    if src.shape != (B, M) or dst.shape != (B, M) or active.shape != (B, M):
        raise ValueError(
            f"src/dst/active must all be [B={B}, M]: "
            f"{src.shape} / {dst.shape} / {active.shape}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(U, B),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((M, R, L), arr.dtype),  # row stage
            pltpu.SemaphoreType.DMA((M,)),  # gather sems
            pltpu.SemaphoreType.DMA((M,)),  # scatter sems
            pltpu.SemaphoreType.DMA(()),  # pass-through copy sem
        ],
    )
    kwargs = {}
    if donate:
        # alias indices count the scalar-prefetch args: cache is operand 3
        kwargs["input_output_aliases"] = {3: 0}
    out = pl.pallas_call(
        functools.partial(_kv_move_kernel, copy_through=not donate),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((U, B, S, R, L), arr.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_mode(interpret),
        name="kv_move_rows",
        **kwargs,
    )(src.astype(jnp.int32), dst.astype(jnp.int32), active.astype(jnp.int32),
      arr.reshape(U, B, S, R, L))
    return out.reshape(arr.shape)


# -----------------------------------------------------------------------------
# slot_write_rows — one launch for the whole-slot install / zero lifecycle
# -----------------------------------------------------------------------------


def _slot_write_kernel(n_leaves, slot_ref, *refs):
    """refs: donor_0..L-1, cache_0..L-1, out_0..L-1 (aliased to cache), sem.

    DMAs donor[:, 0] -> out[:, slot] for every leaf in one kernel; starts
    all copies before waiting so the per-leaf transfers overlap.
    """
    L = n_leaves
    donors = refs[:L]
    outs = refs[2 * L:3 * L]
    sem = refs[3 * L]
    slot = slot_ref[0]
    copies = [
        pltpu.make_async_copy(donors[i].at[:, 0], outs[i].at[:, slot], sem.at[i])
        for i in range(L)
    ]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def slot_write_rows_pallas(cache_leaves, donor_leaves, slot, *,
                           interpret: bool | None = None):
    """Write batch row 0 of every donor leaf into batch row ``slot`` of the
    matching cache leaf, in one launch.

    cache_leaves[i]: [U_i, B, ...]; donor_leaves[i]: [U_i, 1, ...] with
    identical dtype and non-batch dims.  ``slot`` may be a traced scalar.
    The outputs alias the cache leaves (in-place; the wrapping jit donates
    the cache).  Returns the list of updated leaves.
    """
    L = len(cache_leaves)
    if L == 0 or len(donor_leaves) != L:
        raise ValueError(f"leaf lists must be equal and non-empty: {L} vs {len(donor_leaves)}")
    for big, one in zip(cache_leaves, donor_leaves):
        if big.ndim < 2 or one.shape != (big.shape[0], 1) + big.shape[2:]:
            raise ValueError(f"donor leaf {one.shape} does not match cache leaf {big.shape}")
        if big.dtype != one.dtype:
            raise ValueError(f"dtype mismatch: cache {big.dtype} vs donor {one.dtype}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 * L),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * L,
        scratch_shapes=[pltpu.SemaphoreType.DMA((L,))],
    )
    return pl.pallas_call(
        functools.partial(_slot_write_kernel, L),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in cache_leaves],
        # operand layout: slot (scalar prefetch), L donors, L caches —
        # cache i is operand 1 + L + i, aliased in place onto output i
        input_output_aliases={1 + L + i: i for i in range(L)},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(interpret),
        name="slot_write_rows",
    )(jnp.reshape(jnp.asarray(slot, jnp.int32), (1,)), *donor_leaves, *cache_leaves)
