"""AWQ groupwise int4 dequant-GEMM Pallas kernel (paper §5.1 serving precision).

Weights are packed two 4-bit values per int8 byte along K (quant/awq.py); the
kernel streams packed tiles HBM→VMEM — half the weight bandwidth of int8, a
quarter of bf16, which is the entire point at decode batch sizes ≤ 16 where
GEMMs are memory-bound — unpacks nibbles and applies the groupwise
``(q - z) * s`` dequant in VMEM, then runs the MXU matmul in f32.

Block constraint: block_k == group_size, so each K step touches exactly one
scale/zero row (no intra-tile group boundary).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(x_ref, qw_ref, s_ref, z_ref, o_ref, acc_s):
    """Grid step (i, j, k).

    x_ref [bm, bk]; qw_ref [bk//2, bn] packed int8; s_ref/z_ref [1, bn]
    (block_k == group_size); acc [bm, bn] f32.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    packed = qw_ref[...]  # [bk//2, bn] int8: low nibble = even k, high = odd k
    lo = (packed & 0xF).astype(jnp.float32)
    hi = ((packed >> 4) & 0xF).astype(jnp.float32)
    bk2, bn = packed.shape
    w = jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)  # interleave along K
    w = (w - z_ref[...].astype(jnp.float32)) * s_ref[...].astype(jnp.float32)

    acc_s[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = acc_s[...].astype(o_ref.dtype)


def int4_matmul_pallas(x, qweight, scales, zeros, *, group_size: int,
                       block_m: int, block_n: int, interpret: bool | None = None):
    """x: [M, K]; qweight: int8 [K//2, N] packed; scales/zeros: [K//g, N].

    block_k is pinned to ``group_size``; shapes pre-padded to block multiples.
    Returns [M, N] in x.dtype.
    """
    M, K2 = x.shape[0], qweight.shape[0]
    K = K2 * 2
    N = qweight.shape[1]
    assert K % group_size == 0
    grid = (M // block_m, N // block_n, K // group_size)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, group_size), lambda i, j, k: (i, k)),
            pl.BlockSpec((group_size // 2, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
    )(x, qweight, scales, zeros)
