"""Split-KV decode attention, single kernel (paper §3.3 "Masked attention").

FlashAttention's decode path on GPU launches ``flash_fwd_splitkv_kernel`` to
let threadblocks share one KV head, then ``..._combine_kernel`` to reduce the
partial (max, sum, acc) triples; the paper fuses the two with an NCCL-LL
in-kernel barrier.  On TPU the split index IS the sequential minor grid
dimension: partial triples accumulate in VMEM scratch across splits, so the
reduction happens in-kernel with zero barriers and one launch — the same
insight, realized through the TPU grid model instead of flag polling.

Differences from tree_attention: queries are one position per sequence, the
mask is implicit (rows < length, read from SMEM), and RoPE for the single new
position is fused into the kernel (the paper fuses position embedding too).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, block_k: int, scale: float):
    """Grid step (b, h, s): KV split s of head h, sequence b.

    len_ref [1, 1] SMEM; q_ref [1, 1, Gn, hd]; k/v_ref [1, bk, 1, hd];
    o_ref [1, 1, Gn, hd]; scratch m/l [Gn, 128], acc [Gn, hd] (f32).
    """
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[0, 0]
    q = q_ref[0, 0].astype(jnp.float32)  # [Gn, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)  # [bk, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)
    gn = q.shape[0]

    rows = s * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    mask = jnp.broadcast_to(rows < length, (gn, block_k))

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(mask, scores, NEG)

    m_prev = m_s[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new) * mask
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_s[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(s == pl.num_programs(2) - 1)
    def _finish():
        l = l_s[:, :1]
        out = acc_s[...] / jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def decode_attention_pallas(q_r, k, v, length, *, scale: float, block_k: int,
                            interpret: bool | None = None):
    """q_r: [B, Hkv, G, hd]; k/v: [B, S, Hkv, hd]; length: i32 [B, 1].

    Pre-padded shapes (S % block_k == 0).  Returns [B, Hkv, G, hd].
    """
    B, hkv, g, hd = q_r.shape
    S = k.shape[1]
    if S % block_k:
        raise ValueError(
            f"decode_attention: S={S} must be a multiple of block_k="
            f"{block_k} — the floor-div grid would silently drop the "
            f"remainder (pad via kernels.ops)")
    grid = (B, hkv, S // block_k)

    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, s: (b, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, s: (b, s, h, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, s: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hkv, g, hd), q_r.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
    )(length, q_r, k, v)
