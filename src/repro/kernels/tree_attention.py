"""Tree-masked attention Pallas kernel (paper §3.1 non-square mask, §3.3).

One kernel serves draft expansion (w leaves vs prefix+tree), target
verification (bs nodes vs prefix+subgraph) and plain decode (n=1, causal
mask) — the paper's masked-attention operator with a general [n, S] mask.

TPU adaptation (DESIGN.md §3): the GPU kernel splits KV across threadblocks
and combines partial (max, sum, acc) via the NCCL-LL flag protocol; here the
KV split is the *sequential minor grid dimension* — running max / sum / acc
accumulators live in VMEM scratch across KV-block steps, so the combine needs
no barrier and no second kernel launch at all.

Layout: grid (B, Hkv, S/bk); every (b, h) step streams K/V tiles
[bk, hd] and the mask tile [n, bk] HBM→VMEM while the [G·n, hd] query block
stays resident.  K/V arrive as a [B, S, Hkv·hd] view of the cache, so head h
is the h-th 128-lane column block: every block's last two dims are (bk, hd),
which the TPU's (8, 128) tiling rule requires (a [1, bk, 1, hd] block of the
4-D cache is refused by Mosaic).  All matmul tiles are 128-aligned (ops.py
pads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_s, l_s, acc_s, *, g: int, scale: float):
    """Grid step (b, h, s): one KV tile against the resident query block.

    q_ref   [1, 1, Gn, hd]  (g-major: row g*n + i is group g of query i)
    k_ref   [1, bk, hd]     (column block h of the [B, S, Hkv*hd] view)
    v_ref   [1, bk, hd]
    mask_ref[1, n, bk]
    o_ref   [1, 1, Gn, hd]
    scratch m_s/l_s [Gn, 128] f32, acc_s [Gn, hd] f32
    """
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)  # [Gn, hd]
    k = k_ref[0].astype(jnp.float32)  # [bk, hd]
    v = v_ref[0].astype(jnp.float32)  # [bk, hd]
    n, bk = mask_ref.shape[1], mask_ref.shape[2]
    gn = q.shape[0]
    mask = jnp.broadcast_to(mask_ref[0][None], (g, n, bk)).reshape(gn, bk)

    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [Gn, bk]
    scores = jnp.where(mask, scores, NEG)

    m_prev = m_s[:, :1]  # [Gn, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    # fully-masked tiles keep m at NEG; p must be zero there, not exp(0)
    p = jnp.exp(scores - m_new) * mask  # [Gn, bk]
    alpha = jnp.exp(m_prev - m_new)  # [Gn, 1]
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
        out = jnp.where(l > 0, out, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def tree_attention_pallas(q_r, k, v, mask, *, scale: float, block_k: int,
                          interpret: bool | None = None):
    """q_r: [B, Hkv, Gn, hd] g-major; k/v: [B, S, Hkv * hd]; mask: [B, n, S].

    Shapes must be pre-padded: S % block_k == 0, hd/Gn MXU-aligned.
    ``scale`` is 1/sqrt(true head_dim) — hd here may be padded.
    Returns [B, Hkv, Gn, hd].
    """
    B, hkv, gn, hd = q_r.shape
    S = k.shape[1]
    n = mask.shape[1]
    if k.shape != (B, S, hkv * hd) or v.shape != k.shape:
        raise ValueError(
            f"tree_attention: k/v must be the [B, S, Hkv*hd] = {(B, S, hkv * hd)} "
            f"view of the cache, got {k.shape} / {v.shape}")
    if S % block_k or gn % n:
        raise ValueError(
            f"tree_attention: S={S} must be a multiple of block_k={block_k} "
            f"and Gn={gn} of n={n} — the floor-div grid would silently drop "
            f"the remainder (pad via kernels.ops)")
    g = gn // n
    grid = (B, hkv, S // block_k)

    return pl.pallas_call(
        functools.partial(_kernel, g=g, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gn, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, block_k, hd), lambda b, h, s: (b, s, h)),
            pl.BlockSpec((1, n, block_k), lambda b, h, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, gn, hd), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hkv, gn, hd), q_r.dtype),
        scratch_shapes=[
            pltpu.VMEM((gn, 128), jnp.float32),
            pltpu.VMEM((gn, 128), jnp.float32),
            pltpu.VMEM((gn, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
        name="tree_attention",
    )(q_r, k, v, mask)
