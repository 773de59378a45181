"""Fused SwiGLU Pallas kernel (paper §3.3 "Fused SwiGLU").

SwiGLU(x, W, V) = silu(xW) ⊙ (xV).  The paper's GPU kernel computes the same
tile of both matmuls in one threadblock so x is loaded from HBM once and the
σ·⊙ epilogue runs before the store; here each (i, j) grid cell streams x and
the matching W / V tiles HBM→VMEM, accumulates BOTH products in f32 VMEM
scratch over the sequential K dimension, and applies silu(g)·u in-register on
the last K step — x read once, no intermediate HBM round-trip, one launch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(x_ref, wg_ref, wu_ref, o_ref, g_s, u_s):
    """Grid step (i, j, k): x tile [bm, bk] against wg/wu tiles [bk, bn]."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        g_s[...] = jnp.zeros_like(g_s)
        u_s[...] = jnp.zeros_like(u_s)

    x = x_ref[...]
    g_s[...] += jax.lax.dot_general(
        x, wg_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    u_s[...] += jax.lax.dot_general(
        x, wu_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        g = g_s[...]
        o_ref[...] = (g * jax.nn.sigmoid(g) * u_s[...]).astype(o_ref.dtype)


def fused_swiglu_pallas(x, wg, wu, *, block_m: int, block_n: int, block_k: int,
                        interpret: bool | None = None):
    """x: [M, K]; wg, wu: [K, N] — pre-padded to block multiples.

    Returns silu(x@wg) * (x@wu), [M, N].
    """
    M, K = x.shape
    N = wg.shape[1]
    if M % block_m or N % block_n or K % block_k:
        raise ValueError(
            f"fused_swiglu: shapes M={M}, N={N}, K={K} must be multiples of "
            f"blocks ({block_m}, {block_n}, {block_k}) — the floor-div grid "
            f"would silently drop the remainder (pad via kernels.ops)")
    grid = (M // block_m, N // block_n, K // block_k)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
        name="fused_swiglu",
    )(x, wg, wu)
