"""Jit'd public wrappers around the Pallas kernels.

Each wrapper pads inputs to MXU-aligned block multiples (128 lanes, 8
sublanes), lays tensors out for the kernel grid, and un-pads the result.
Padding is semantics-preserving: padded KV rows are masked False, padded
matmul K columns are zero, padded query rows are sliced off.

``interpret`` defaults to None everywhere: the kernels run in the Pallas
interpreter on the CPU backend and compile to Mosaic on a TPU
(``repro.kernels.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.flags import get_flags
from repro.sharding import shard_local
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.fused_swiglu import fused_swiglu_pallas
from repro.kernels.int4_matmul import int4_matmul_pallas
from repro.kernels.kv_moves import kv_move_rows_pallas, slot_write_rows_pallas
from repro.kernels.ref import kv_move_rows_ref
from repro.kernels.tree_attention import tree_attention_pallas


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_dim(x, axis: int, to: int):
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# -----------------------------------------------------------------------------
# tree attention
# -----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def tree_attention(q, k, v, mask, *, block_k: int = 128, interpret: bool | None = None):
    """q: [B, n, Hq, hd]; k, v: [B, S, Hkv, hd]; mask: bool [B, n, S].

    The paper's non-square tree-masked attention; returns [B, n, Hq, hd].
    """
    B, n, hq, hd = q.shape
    S, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)

    hd_p = _ceil_to(hd, 128)
    S_p = _ceil_to(S, block_k)
    n_p = _ceil_to(n, 8)

    qp = _pad_dim(_pad_dim(q, 3, hd_p), 1, n_p)
    kp = _pad_dim(_pad_dim(k, 3, hd_p), 1, S_p)
    vp = _pad_dim(_pad_dim(v, 3, hd_p), 1, S_p)
    mp = _pad_dim(_pad_dim(mask, 2, S_p), 1, n_p)

    # g-major query layout: [B, Hkv, G*n_p, hd]
    q_r = qp.reshape(B, n_p, hkv, g, hd_p).transpose(0, 2, 3, 1, 4).reshape(B, hkv, g * n_p, hd_p)

    # [B, S, Hkv*hd]: a free view that puts head h in lane block h
    kp, vp = kp.reshape(B, S_p, hkv * hd_p), vp.reshape(B, S_p, hkv * hd_p)
    out = tree_attention_pallas(q_r, kp, vp, mp, scale=scale, block_k=block_k, interpret=interpret)
    out = out.reshape(B, hkv, g, n_p, hd_p).transpose(0, 3, 1, 2, 4).reshape(B, n_p, hq, hd_p)
    return out[:, :n, :, :hd]


# -----------------------------------------------------------------------------
# decode attention (split-KV, fused combine)
# -----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, length, *, block_k: int = 128, interpret: bool | None = None):
    """q: [B, Hq, hd]; k, v: [B, S, Hkv, hd]; length: i32 [B].

    One-position decode against rows [0, length); returns [B, Hq, hd].
    """
    B, hq, hd = q.shape
    S, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = 1.0 / (hd ** 0.5)

    hd_p = _ceil_to(hd, 128)
    g_p = _ceil_to(g, 8)
    S_p = _ceil_to(S, block_k)

    qp = _pad_dim(q, 2, hd_p).reshape(B, hkv, g, hd_p)
    qp = _pad_dim(qp, 2, g_p)
    kp = _pad_dim(_pad_dim(k, 3, hd_p), 1, S_p)
    vp = _pad_dim(_pad_dim(v, 3, hd_p), 1, S_p)

    out = decode_attention_pallas(
        qp, kp, vp, length.reshape(B, 1).astype(jnp.int32),
        scale=scale, block_k=block_k, interpret=interpret,
    )
    return out[:, :, :g, :hd].reshape(B, hq, hd)


# -----------------------------------------------------------------------------
# fused SwiGLU
# -----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_swiglu(x, wg, wu, *, interpret: bool | None = None):
    """x: [T, d]; wg, wu: [d, ff] -> silu(x@wg) * (x@wu), [T, ff]."""
    T, K = x.shape
    N = wg.shape[1]
    bm = 8 if T <= 64 else 128
    bn, bk = 128, 128
    T_p, K_p, N_p = _ceil_to(T, bm), _ceil_to(K, bk), _ceil_to(N, bn)

    xp = _pad_dim(_pad_dim(x, 0, T_p), 1, K_p)
    wgp = _pad_dim(_pad_dim(wg, 0, K_p), 1, N_p)
    wup = _pad_dim(_pad_dim(wu, 0, K_p), 1, N_p)
    out = fused_swiglu_pallas(xp, wgp, wup, block_m=bm, block_n=bn, block_k=bk, interpret=interpret)
    return out[:T, :N]


# -----------------------------------------------------------------------------
# KV-reorganization row moves (cache compaction / re-root, paper §3.2)
# -----------------------------------------------------------------------------
# Unlike the kernels above these are NOT separately jitted: they are only
# ever called inside the engine's already-jitted round programs, and the
# fused/reference choice is a trace-time flag (use_pallas_kv_moves) exactly
# like the attention kernel selection in models/attention.py.  Under a
# multi-device mesh the fused kernels run per shard (``shard_local``): each
# chip moves the rows of its own kv heads.


def kv_move_rows(arr, src, dst, mask, *, donate: bool = False, axes=None):
    """Move rows of one cache leaf: arr [U, B, S, ...]; src/dst i32 [B, M];
    mask bool [B, M].  Parallel-assignment semantics (sources read before any
    write); entries with mask False, src < 0, or dst < 0 are dropped.

    ``donate=True`` may update in place (the fused kernel aliases its output
    onto the input) — callers must own the buffer, i.e. the wrapping jit
    donates the cache.  ``donate=False`` never mutates the input: the
    speculative-lookahead contract (kv.py) requires the retained pre-reroot
    snapshot to survive this call.  ``axes``: the leaf's logical axes, which
    place the fused kernel on the mesh (replicated when None).
    """
    flags = get_flags()
    M = src.shape[1]
    if M == 0:
        return arr
    if not flags.use_pallas_kv_moves:
        return kv_move_rows_ref(arr, src, dst, mask)
    active = (mask & (src >= 0) & (dst >= 0)).astype(jnp.int32)

    def local(a, s, d, act):
        U, B, S = a.shape[:3]
        out = kv_move_rows_pallas(a.reshape(U, B, S, -1), s, d, act, donate=donate)
        return out.reshape(a.shape)

    axes = axes or (None,) * arr.ndim
    plan = (None, None)
    return shard_local(local, (arr, src, dst, active), (axes, plan, plan, plan), axes,
                       local_dims=(tuple(range(3, arr.ndim)), (), (), ()))


def slot_write_rows(cache_leaves, donor_leaves, slot, axes=None):
    """Fused slot lifecycle write: donor[:, 0] -> cache[:, slot] for every
    leaf in ONE kernel launch (vs one XLA update per leaf).  Returns the
    updated leaves, or None when the leaves don't fit the kernel's contract
    (shape/dtype mismatch, empty tree) — callers fall back to the per-leaf
    XLA path, which is also the flag-off default.  ``axes``: each leaf's
    logical axes, which place the kernel on the mesh (replicated when None)."""
    flags = get_flags()
    if not flags.use_pallas_kv_moves or not cache_leaves:
        return None
    if len(cache_leaves) != len(donor_leaves):
        return None
    for big, one in zip(cache_leaves, donor_leaves):
        if big.ndim < 2 or one.shape != (big.shape[0], 1) + big.shape[2:]:
            return None
        if big.dtype != one.dtype:
            return None
    L = len(cache_leaves)
    axes = list(axes or [(None,) * c.ndim for c in cache_leaves])

    def local(*args):
        return slot_write_rows_pallas(list(args[L:2 * L]), list(args[:L]), args[-1])

    return shard_local(
        local, (*donor_leaves, *cache_leaves, jnp.asarray(slot, jnp.int32)),
        axes + axes + [()], axes,
        local_dims=[tuple(range(2, c.ndim)) for c in cache_leaves] * 2 + [()])


# -----------------------------------------------------------------------------
# int4 AWQ dequant-GEMM
# -----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("group_size", "interpret"))
def int4_matmul(x, qweight, scales, zeros, *, group_size: int = 128,
                interpret: bool | None = None):
    """x: [T, K]; qweight: int8 [K//2, N] (packed pairs along K);
    scales/zeros: [K//group_size, N].  Returns [T, N] in x.dtype.

    K must already be a multiple of group_size (quantization granularity).
    """
    T, K = x.shape
    N = qweight.shape[1]
    assert K % group_size == 0 and qweight.shape[0] * 2 == K
    bm = 8 if T <= 64 else 128
    bn = 128
    T_p, N_p = _ceil_to(T, bm), _ceil_to(N, bn)

    xp = _pad_dim(x, 0, T_p)
    qwp = _pad_dim(qweight, 1, N_p)
    sp = _pad_dim(scales, 1, N_p)
    zp = _pad_dim(zeros, 1, N_p)
    out = int4_matmul_pallas(
        xp, qwp, sp, zp, group_size=group_size, block_m=bm, block_n=bn, interpret=interpret
    )
    return out[:T, :N]
