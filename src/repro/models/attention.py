"""GQA/MQA attention: full-sequence (train/prefill), cached (decode/spec-tree),
and cross-attention against stub encoder states.

Cached mode takes an explicit ``[B, n, S_max]`` attention mask — this is the
paper's *non-square tree mask* (§3.1 "Non-square mask support"): the n query
rows are draft leaves / verification nodes attending the prefix cache plus
their tree ancestors.  All cache writes are masked one-hot scatters (never
dynamic-slice on the sharded sequence dim), so the sequence-sharded KV cache
("kv_seq" -> "model") updates without collectives; the softmax over the
sharded KV axis is XLA's distributed reduction — the mesh-scale analogue of
the paper's split-KV single-kernel combine.  The serving engine traces under
``SERVING_RULES`` instead, which shard the cache over its kv heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.flags import get_flags
from repro.models.common import apply_rope, dense_init, zeros_init
from repro.sharding import constrain, shard_local

# logical axes of one layer's K/V cache leaf [B, S, Hkv, hd]: under the
# default rules the sequence shards over "model"; under the serving rules
# (SpecEngine) the kv heads do
KV_AXES = ("cache_batch", "kv_seq", "kv_heads", None)
Q_AXES = ("batch", None, "heads", None)


def init_attention(cfg, key, *, cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": dense_init(ks[0], (d, hq, hd), ("embed", "heads", "head_dim"), dt),
        "wk": dense_init(ks[1], (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wv": dense_init(ks[2], (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dt),
        "wo": dense_init(ks[3], (hq, hd, d), ("heads", "head_dim", "embed"), dt, scale=(hq * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((hq, hd), ("heads", "head_dim"), dt)
        p["bk"] = zeros_init((hkv, hd), ("kv_heads", "head_dim"), dt)
        p["bv"] = zeros_init((hkv, hd), ("kv_heads", "head_dim"), dt)
    return p


def _project_qkv(cfg, p, x, positions, *, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].value)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].value)
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].value)
    if "bq" in p:
        q = q + p["bq"].value
        k = k + p["bk"].value
        v = v + p["bv"].value
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _grouped_scores(q, k):
    """q [B,n,Hq,hd], k [B,S,Hkv,hd] -> scores [B,Hkv,G,n,S] (GQA grouping)."""
    B, n, hq, hd = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(B, n, hkv, g, hd)
    return jnp.einsum("bnkgh,bskh->bkgns", qg, k) / jnp.sqrt(hd).astype(jnp.float32)


def _attend(q, k, v, mask):
    """Masked softmax attention. mask broadcastable to [B,Hkv,G,n,S]."""
    scores = _grouped_scores(q, k).astype(jnp.float32)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # guard fully-masked rows (padded queries)
    probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bkgns,bskh->bnkgh", probs.astype(v.dtype), v)
    B, n, hkv, g, hd = out.shape
    return out.reshape(B, n, hkv * g, hd)


def attention_full(cfg, p, x, positions, *, enc=None):
    """Full-sequence attention (train / prefill), q-chunked over the sequence.

    Returns (out [B,S,d], (k, v) computed K/V for cache population).
    ``enc`` -> cross-attention (no causal mask, no rope, K/V from enc).
    """
    flags = get_flags()
    B, S, _ = x.shape
    if enc is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].value)
        k = jnp.einsum("bsd,dhk->bshk", enc, p["wk"].value)
        v = jnp.einsum("bsd,dhk->bshk", enc, p["wv"].value)
        mask = jnp.ones((1, 1, 1, 1, 1), bool)
        out = _attend(q, k, v, mask)
        out = jnp.einsum("bnhk,hkd->bnd", out, p["wo"].value)
        return constrain(out, "batch", "seq", "act_embed"), (k, v)

    q, k, v = _project_qkv(cfg, p, x, positions)
    if flags.seq_shard_acts and flags.attn_heads_tp:
        # Megatron-SP: residuals stay seq-sharded OUTSIDE the block, but the
        # attention itself computes head-parallel — k/v all-gather once per
        # layer instead of psum-ing every q-chunk's seq-sharded scores.
        q = constrain(q, "batch", "seq", "heads", None)
        k = constrain(k, "batch", "seq", "kv_heads", None)
        v = constrain(v, "batch", "seq", "kv_heads", None)
    elif flags.seq_shard_acts:
        # sequence parallelism: K/V shard on seq over "model" (the layout the
        # cache keeps); scores per q-chunk then stay seq-sharded too.
        q = constrain(q, "batch", "act_seq", None, None)
        k = constrain(k, "batch", "kv_seq", None, None)
        v = constrain(v, "batch", "kv_seq", None, None)
    else:
        q = constrain(q, "batch", "seq", "heads", "head_dim")
        k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
        v = constrain(v, "batch", "seq", "kv_heads", "head_dim")

    chunk = min(flags.attn_chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk
    pos_k = positions  # [B,S]

    def one_chunk(ci):
        qc = jax.lax.dynamic_slice_in_dim(q, ci * chunk, chunk, axis=1)
        pos_q = jax.lax.dynamic_slice_in_dim(positions, ci * chunk, chunk, axis=1)
        m = pos_k[:, None, :] <= pos_q[:, :, None]  # causal [B,c,S]
        if cfg.sliding_window:
            m &= pos_k[:, None, :] > (pos_q[:, :, None] - cfg.sliding_window)
        return _attend(qc, k, v, m[:, None, None, :, :])

    if n_chunks == 1:
        out = one_chunk(0)
    else:
        # checkpoint each q-chunk: backward recomputes the chunk's mask and
        # probabilities instead of saving O(S^2/nc) residuals per chunk —
        # the memory-side half of flash attention, in pure XLA.
        outs = jax.lax.map(jax.checkpoint(one_chunk), jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, q.shape[2], q.shape[3])
    out = jnp.einsum("bnhk,hkd->bnd", out, p["wo"].value)
    return constrain(out, "batch", "seq", "act_embed"), (k, v)


def update_rows_contiguous(cache, rows, start):
    """Write ``rows [B,n,...]`` into ``cache [B,S,...]`` at [start, start+n).

    Decode/chain fast path.  Implemented as n per-row iota==row selects, NOT
    dynamic_update_slice: the cache is sequence-sharded over "model", and a
    DUS at a dynamic offset forces GSPMD into involuntary full
    rematerialization (replicate + re-partition), while the select compare is
    shard-local — one read + one write of the cache per row, no collectives.
    (n is the decode/chain chunk, <= 8; the general tree path uses
    scatter_rows below.)
    """
    start = jnp.asarray(start, jnp.int32)
    S = cache.shape[1]
    iota = jnp.arange(S, dtype=jnp.int32)
    n = rows.shape[1]
    bshape = (1, S) + (1,) * (cache.ndim - 2)
    for i in range(n):
        m = (iota == start + i).reshape(bshape)
        row = rows[:, i : i + 1].astype(cache.dtype)  # [B,1,...] broadcasts over S
        cache = jnp.where(m, row, cache)
    return cache


def scatter_rows(cache, rows, row_idx, row_mask=None):
    """Write ``rows [B,n,...]`` into ``cache [B,S,...]`` at ``row_idx [B,n]``.

    One-hot masked scatter: O(S*n) work, no re-layout of the sequence-sharded
    cache, duplicate/-1 indices are dropped via the mask.  This is the
    in-forward KV *write* path only — per-round cache reorganization
    (verify compaction, re-root moves) goes through ``core/kv.apply_moves``
    and the O(moved-rows) kernels in ``kernels/kv_moves.py`` instead.
    """
    B, S = cache.shape[:2]
    n = rows.shape[1]
    valid = row_idx >= 0
    if row_mask is not None:
        valid &= row_mask
    onehot = (row_idx[:, :, None] == jnp.arange(S)[None, None, :]) & valid[:, :, None]
    oh = onehot.astype(cache.dtype)  # [B,n,S]
    flat_r = rows.reshape(B, n, -1)
    flat_c = cache.reshape(B, S, -1)
    upd = jnp.einsum("bns,bnf->bsf", oh, flat_r)
    keep = 1.0 - jnp.einsum("bns->bs", oh).clip(0, 1)
    out = flat_c * keep[..., None].astype(cache.dtype) + upd
    return out.reshape(cache.shape)


def gather_rows(cache, row_idx):
    """Gather rows [B,n,...] from cache [B,S,...]; row_idx -1 -> zeros."""
    B, S = cache.shape[:2]
    n = row_idx.shape[1]
    onehot = (row_idx[:, :, None] == jnp.arange(S)[None, None, :]).astype(cache.dtype)
    flat_c = cache.reshape(B, S, -1)
    out = jnp.einsum("bns,bsf->bnf", onehot, flat_c)
    return out.reshape((B, n) + cache.shape[2:])


def attention_cached(cfg, p, x, cache_k, cache_v, row_idx, positions, attn_mask, *,
                     enc_kv=None, row_start=None):
    """Cached attention for decode / spec-tree forward.

    x: [B, n, d] new tokens; their K/V are written at ``row_idx`` [B, n]
    (absolute cache rows, -1 = skip).  ``attn_mask`` [B, n, S_max] is the
    non-square tree mask (True = attend).  Returns (out, new_k, new_v).
    For cross blocks, pass ``enc_kv=(k, v)`` and attn_mask=None.
    ``row_start``: scalar fast path — rows are [start, start+n) for every
    batch element (decode/chain), written via dynamic_update_slice.
    """
    flags = get_flags()
    if enc_kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].value)
        out = _attend(q, enc_kv[0], enc_kv[1], jnp.ones((1, 1, 1, 1, 1), bool))
        out = jnp.einsum("bnhk,hkd->bnd", out, p["wo"].value)
        return out, None, None

    q, k_new, v_new = _project_qkv(cfg, p, x, positions)
    if row_start is not None:  # contiguous decode/chain rows: cheap in-place
        ck = update_rows_contiguous(cache_k, k_new, row_start)
        cv = update_rows_contiguous(cache_v, v_new, row_start)
    else:
        ck = scatter_rows(cache_k, k_new, row_idx)
        cv = scatter_rows(cache_v, v_new, row_idx)
    ck = constrain(ck, *KV_AXES)
    cv = constrain(cv, *KV_AXES)

    if flags.use_pallas_attention:
        from repro.kernels import ops as kops

        # per kv-head shard: each chip attends with its own heads
        out = shard_local(
            kops.tree_attention, (q, ck, cv, attn_mask),
            (Q_AXES, KV_AXES, KV_AXES, ("batch", None, "kv_seq")), Q_AXES,
            local_dims=((0, 2), (0, 2), (0, 2), (0,)))
    else:
        out = _attend(q, ck, cv, attn_mask[:, None, None, :, :])
    out = jnp.einsum("bnhk,hkd->bnd", out, p["wo"].value)
    return out, ck, cv
