"""Collective matmul: the TPU-native analogue of the paper's fused
GEMM + all-reduce (§3.3, DESIGN.md §3).

The GPU kernel interleaves GEMM tiles with NCCL-LL stores so communication
rides inside the compute kernel.  On TPU the equivalent transformation is a
ring decomposition under shard_map: each step multiplies the locally-resident
activation shard against the weight shard and ``ppermute``s the activation to
the next neighbour, so per-step ICI transfer overlaps the next MXU step (the
XLA latency-hiding scheduler pipelines the permute with the dot).  Two
variants:

  rs_matmul  — reduce-scatter-style: y_partial computed per step, summed into
               the shard each device owns (GEMM + all-reduce fused; output
               row-sharded, exactly what the next layer wants under TP).
  ag_matmul  — all-gather-style: activation shards stream around the ring and
               accumulate into the full product (output replicated).

Used by the §Perf hillclimb through flags.collective_matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def matmul_allreduce(x, w, mesh, axis: str = "model"):
    """y = x @ w with w K-sharded over ``axis``; all-reduce fused via
    reduce-scatter + all-gather (the ring schedule XLA pipelines on ICI).

    x: [M, K] replicated activations; w: [K, N] sharded on K.
    """
    def body(x_loc, w_loc):
        part = jnp.einsum("mk,kn->mn", x_loc, w_loc)
        scat = jax.lax.psum_scatter(part, axis, scatter_dimension=1, tiled=True)
        return jax.lax.all_gather(scat, axis, axis=1, tiled=True)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, None),
        check_vma=False,
    )(x, w)


def matmul_ag_pipelined(x, w, mesh, axis: str = "model"):
    """y = x @ w with x K-sharded; activation shards ride the ring while each
    local GEMM runs (collective-matmul proper: O(K/p) resident activations).
    """
    def body(x_loc, w_loc):
        # static axis extent from the mesh (jax.lax.axis_size is newer jax,
        # and the ring permutation below needs a Python int anyway)
        p = mesh.shape[axis]
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % p) for i in range(p)]
        kshard = w_loc.shape[0] // p

        def step(carry, i):
            x_cur, acc = carry
            src = (idx - i) % p  # which K-shard x_cur holds at step i
            wk = jax.lax.dynamic_slice_in_dim(w_loc, src * kshard, kshard, axis=0)
            acc = acc + jnp.einsum("mk,kn->mn", x_cur, wk)
            x_nxt = jax.lax.ppermute(x_cur, axis, perm)
            return (x_nxt, acc), None

        acc0 = jnp.zeros((x_loc.shape[0], w_loc.shape[1]), x_loc.dtype)
        (_, acc), _ = jax.lax.scan(step, (x_loc, acc0), jnp.arange(p))
        return acc

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )(x, w)
