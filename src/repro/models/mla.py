"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style).

Queries come through a compressed latent (``w_dq -> q_norm -> w_uq``) when
``q_lora_rank`` is set, else straight from one ``w_q`` (DeepSeek-V2-Lite).
With YaRN (``cfg.yarn``) the rotary frequencies are scaled and the softmax
scale is multiplied by its ``softmax_factor``.

The KV cache stores only the compressed latent ``c_kv`` plus the shared
rotary key ``k_rope`` — the MLA memory win.  Cached mode uses the *absorbed*
formulation (W_uk folded into the query, W_uv applied after the probability-
weighted latent sum), so decode never materializes per-head K/V.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense_init, ones_init, rms_norm
from repro.models.attention import scatter_rows
from repro.sharding import constrain


def init_mla(cfg, key):
    d, H = cfg.d_model, cfg.n_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    nd, rd, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.param_dtype)
    if ql:
        q = {"w_dq": dense_init(ks[0], (d, ql), ("embed", "lora"), dt),
             "q_norm": ones_init((ql,), ("lora",), dt),
             "w_uq": dense_init(ks[1], (ql, H, nd + rd), ("lora", "heads", "qk_dim"), dt)}
    else:
        q = {"w_q": dense_init(ks[0], (d, H, nd + rd), ("embed", "heads", "qk_dim"), dt)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], (d, kvl + rd), ("embed", "lora"), dt),
        "kv_norm": ones_init((kvl,), ("lora",), dt),
        "w_uk": dense_init(ks[3], (kvl, H, nd), ("lora", "heads", "qk_dim"), dt),
        "w_uv": dense_init(ks[4], (kvl, H, vd), ("lora", "heads", "head_dim"), dt),
        "wo": dense_init(ks[5], (H, vd, d), ("heads", "head_dim", "embed"), dt, scale=(H * vd) ** -0.5),
    }


def _queries(cfg, p, x, positions):
    nd = cfg.nope_head_dim
    if cfg.q_lora_rank:
        q_lat = rms_norm(x @ p["w_dq"].value, p["q_norm"].value, cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", q_lat, p["w_uq"].value)  # [B,S,H,nd+rd]
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].value)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.yarn)
    return q_nope, q_rope


def _latents(cfg, p, x, positions):
    kvl, rd = cfg.kv_lora_rank, cfg.rope_head_dim
    lat = x @ p["w_dkv"].value  # [B,S,kvl+rd]
    c_kv = rms_norm(lat[..., :kvl], p["kv_norm"].value, cfg.norm_eps)
    k_rope = apply_rope(lat[..., None, kvl:], positions, cfg.rope_theta, cfg.yarn)[..., 0, :]
    return c_kv, k_rope


def _softmax_scale(cfg):
    scale = 1.0 / jnp.sqrt(cfg.nope_head_dim + cfg.rope_head_dim)
    return scale if cfg.yarn is None else scale * cfg.yarn.softmax_factor


def mla_full(cfg, p, x, positions):
    """Train/prefill MLA: materialized K/V, causal, q-chunked.

    Returns (out, (c_kv, k_rope)) for cache population.
    """
    from repro.flags import get_flags

    B, S, _ = x.shape
    vd = cfg.v_head_dim
    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_kv, k_rope = _latents(cfg, p, x, positions)
    k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uk"].value)
    v = jnp.einsum("bsl,lhk->bshk", c_kv, p["w_uv"].value)
    k_nope = constrain(k_nope, "batch", "seq", "heads", "qk_dim")
    v = constrain(v, "batch", "seq", "heads", "head_dim")
    scale = _softmax_scale(cfg)

    chunk = min(get_flags().attn_chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk

    def one_chunk(ci):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, ci * chunk, chunk, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, ci * chunk, chunk, axis=1)
        pos_q = jax.lax.dynamic_slice_in_dim(positions, ci * chunk, chunk, axis=1)
        scores = (
            jnp.einsum("bnhk,bshk->bhns", qn, k_nope)
            + jnp.einsum("bnhk,bsk->bhns", qr, k_rope)
        ) * scale
        mask = positions[:, None, :] <= pos_q[:, :, None]  # [B,c,S]
        scores = jnp.where(mask[:, None, :, :], scores.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhns,bshk->bnhk", probs, v)

    if n_chunks == 1:
        out = one_chunk(0)
    else:
        # per-chunk checkpoint: recompute probs in backward (see attention.py)
        outs = jax.lax.map(jax.checkpoint(one_chunk), jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, cfg.n_heads, vd)
    out = jnp.einsum("bnhk,hkd->bnd", out, p["wo"].value)
    return constrain(out, "batch", "seq", "act_embed"), (c_kv, k_rope)


def mla_cached(cfg, p, x, cache_ckv, cache_krope, row_idx, positions, attn_mask, *,
               row_start=None):
    """Cached MLA (decode / spec tree), absorbed form. Returns (out, ckv', krope')."""
    from repro.models.attention import update_rows_contiguous

    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_new, kr_new = _latents(cfg, p, x, positions)
    if row_start is not None:  # contiguous decode/chain fast path
        ckv = update_rows_contiguous(cache_ckv, c_new, row_start)
        krope = update_rows_contiguous(cache_krope, kr_new, row_start)
    else:
        ckv = scatter_rows(cache_ckv, c_new, row_idx)
        krope = scatter_rows(cache_krope, kr_new, row_idx)
    ckv = constrain(ckv, "cache_batch", "kv_seq", None)
    krope = constrain(krope, "cache_batch", "kv_seq", None)

    # absorbed: q_eff[h] = q_nope[h] @ W_uk[h]^T -> dot with latent directly
    q_eff = jnp.einsum("bnhk,lhk->bnhl", q_nope, p["w_uk"].value)
    scale = _softmax_scale(cfg)
    scores = (
        jnp.einsum("bnhl,bsl->bhns", q_eff, ckv)
        + jnp.einsum("bnhk,bsk->bhns", q_rope, krope)
    ) * scale
    scores = jnp.where(attn_mask[:, None, :, :], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.any(attn_mask[:, None, :, :], axis=-1, keepdims=True), probs, 0.0)
    lat_sum = jnp.einsum("bhns,bsl->bnhl", probs.astype(ckv.dtype), ckv)
    out = jnp.einsum("bnhl,lhk->bnhk", lat_sum, p["w_uv"].value)
    out = jnp.einsum("bnhk,hkd->bnd", out, p["wo"].value)
    return out, ckv, krope
