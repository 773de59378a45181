"""Generic decoder assembly: block plans, scan-over-units, caches.

Every architecture reduces to a *plan*: a list of groups, each group a
repeating unit of block kinds scanned ``n_reps`` times.

  dense arch        -> [ (("dense",), n_layers) ]
  mixtral           -> [ (("moe",), 56) ]
  deepseek-moe      -> [ (("dense",), 1), (("moe",), 27) ]        (first_k_dense)
  zamba2            -> [ (("mamba2",)*6 + ("shared",), 9) ]       (shared weights)
  llama-3.2-vision  -> [ (("dense",)*4 + ("cross",), 20) ]
  rwkv6             -> [ (("rwkv6",), 32) ]

Two modes:
  "full"   — whole-sequence causal (train; prefill when make_cache=S_max)
  "cached" — n new tokens against an existing cache with explicit non-square
             attention masks (decode / spec-tree / chain verification)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.flags import get_flags
from repro.models import mamba2 as m2
from repro.models import mla as mla_mod
from repro.models import rwkv6 as rk
from repro.models.attention import KV_AXES, attention_cached, attention_full, init_attention
from repro.models.common import dense_init, ones_init, rms_norm
from repro.sharding import Param, add_leading_axis, constrain, shard_local


# -----------------------------------------------------------------------------
# Plans
# -----------------------------------------------------------------------------


def build_plan(cfg):
    """Returns list of (unit_def: tuple[str], n_reps: int)."""
    plan = []
    first_k = getattr(cfg, "first_k_dense", 0)
    n_main = cfg.n_layers - first_k
    if first_k:
        plan.append((("dense",), first_k))
    if cfg.shared_attn_every:
        k = cfg.shared_attn_every
        assert n_main % k == 0, (cfg.name, n_main, k)
        plan.append((tuple(cfg.block_pattern) * k + ("shared",), n_main // k))
    else:
        pat = tuple(cfg.block_pattern)
        assert n_main % len(pat) == 0, (cfg.name, n_main, pat)
        plan.append((pat, n_main // len(pat)))
    return plan


@dataclasses.dataclass
class Ctx:
    """Per-call context threaded to every block (closure, not scanned)."""

    mode: str  # "full" | "cached"
    make_cache: int = 0  # S_max when prefill should emit a cache
    positions: Any = None  # [B, n] absolute rope positions
    row_idx: Any = None  # [B, n] cache rows for new K/V (-1 = skip)
    attn_mask: Any = None  # [B, n, S_max] non-square mask (cached mode)
    enc: Any = None  # [B, n_enc, d] stub encoder states (cross blocks)
    commit_mask: Any = None  # [B, n] chain-mode state commit mask
    x0: Any = None  # original embeddings (zamba shared block input)
    row_start: Any = None  # scalar: rows are [start, start+n) for ALL batch
    #   elements (decode/chain path) -> cache writes use dynamic_update_slice
    #   instead of the onehot scatter (§Perf: kills the full-cache rewrite)


# -----------------------------------------------------------------------------
# Block init
# -----------------------------------------------------------------------------


def _init_mlp(cfg, key):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "wg": dense_init(ks[0], (d, ff), ("embed", "ff"), dt),
        "wu": dense_init(ks[1], (d, ff), ("embed", "ff"), dt),
        "wd": dense_init(ks[2], (ff, d), ("ff", "embed"), dt),
    }


def init_block(cfg, kind, key):
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("dense", "moe", "cross"):
        if cfg.attn_kind == "mla" and kind != "cross":
            attn = mla_mod.init_mla(cfg, k1)
        else:
            attn = init_attention(cfg, k1, cross=(kind == "cross"))
        if kind == "moe":
            from repro.models.moe import init_moe

            mlp = init_moe(cfg, k2)
        else:
            mlp = _init_mlp(cfg, k2)
        return {
            "ln1": ones_init((d,), ("act_embed",), dt),
            "attn": attn,
            "ln2": ones_init((d,), ("act_embed",), dt),
            "mlp": mlp,
        }
    if kind == "mamba2":
        return {"ln": ones_init((d,), ("act_embed",), dt), "mamba": m2.init_mamba2(cfg, k1)}
    if kind == "rwkv6":
        return {
            "ln1": ones_init((d,), ("act_embed",), dt),
            "tm": rk.init_rwkv6(cfg, k1),
            "ln2": ones_init((d,), ("act_embed",), dt),
        }
    if kind == "shared":
        # per-invocation input projection over concat(h, x0); weights of the
        # inner attn+mlp are SHARED across invocations (stored model-level).
        return {"in_w": dense_init(k1, (2 * d, d), ("embed", "embed"), dt)}
    raise ValueError(kind)


def init_shared_attn(cfg, key):
    d = cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    return {
        "ln1": ones_init((d,), ("act_embed",), dt),
        "attn": init_attention(cfg, k1),
        "ln2": ones_init((d,), ("act_embed",), dt),
        "mlp": _init_mlp(cfg, k2),
    }


# -----------------------------------------------------------------------------
# Block caches
# -----------------------------------------------------------------------------


def init_block_cache(cfg, kind, B, S_max, dtype):
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    if kind in ("dense", "moe"):
        if cfg.attn_kind == "mla":
            return {
                "ckv": jnp.zeros((B, S_max, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((B, S_max, cfg.rope_head_dim), dtype),
            }
        return {
            "k": jnp.zeros((B, S_max, hkv, hd), dtype),
            "v": jnp.zeros((B, S_max, hkv, hd), dtype),
        }
    if kind == "cross":
        return {
            "ek": jnp.zeros((B, cfg.n_enc_tokens, hkv, hd), dtype),
            "ev": jnp.zeros((B, cfg.n_enc_tokens, hkv, hd), dtype),
        }
    if kind == "mamba2":
        return m2.init_mamba_cache(cfg, B, dtype)
    if kind == "rwkv6":
        return rk.init_rwkv_cache(cfg, B, dtype)
    if kind == "shared":
        return {
            "k": jnp.zeros((B, S_max, hkv, hd), dtype),
            "v": jnp.zeros((B, S_max, hkv, hd), dtype),
        }
    raise ValueError(kind)


# -----------------------------------------------------------------------------
# Block apply
# -----------------------------------------------------------------------------


def _mlp_apply(cfg, p, x):
    flags = get_flags()
    if flags.use_pallas_swiglu:
        from repro.kernels import ops as kops

        B, S, d = x.shape
        # per ff-column shard: each chip computes its slice of the hidden
        w_axes = ("embed", "ff")
        out = shard_local(
            kops.fused_swiglu, (x.reshape(B * S, d), p["wg"].value, p["wu"].value),
            ((None, "act_embed"), w_axes, w_axes), (None, "ff"),
            local_dims=((), (1,), (1,)))
        return (out @ p["wd"].value).reshape(B, S, d)
    g = x @ p["wg"].value
    u = x @ p["wu"].value
    h = jax.nn.silu(g) * u
    h = constrain(h, "batch", "seq", "ff")
    return h @ p["wd"].value


def _attn_dispatch(cfg, p, h, ctx: Ctx, cache, kind):
    """Run the attention sub-block in the right mode; returns (out, new_cache)."""
    if kind == "cross":
        if ctx.mode == "full":
            out, (ek, ev) = attention_full(cfg, p, h, None, enc=ctx.enc)
            nc = {"ek": ek, "ev": ev} if ctx.make_cache else None
            return out, nc
        out, _, _ = attention_cached(
            cfg, p, h, None, None, None, None, None, enc_kv=(cache["ek"], cache["ev"])
        )
        return out, dict(cache)

    if cfg.attn_kind == "mla":
        if ctx.mode == "full":
            out, (ckv, krope) = mla_mod.mla_full(cfg, p, h, ctx.positions)
            nc = None
            if ctx.make_cache:
                pad = ctx.make_cache - ckv.shape[1]
                nc = {
                    "ckv": constrain(jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
                                     "cache_batch", "kv_seq", None),
                    "krope": constrain(jnp.pad(krope, ((0, 0), (0, pad), (0, 0))),
                                       "cache_batch", "kv_seq", None),
                }
            return out, nc
        out, ckv, krope = mla_mod.mla_cached(
            cfg, p, h, cache["ckv"], cache["krope"], ctx.row_idx, ctx.positions,
            ctx.attn_mask, row_start=ctx.row_start
        )
        return out, {"ckv": ckv, "krope": krope}

    if ctx.mode == "full":
        out, (k, v) = attention_full(cfg, p, h, ctx.positions)
        nc = None
        if ctx.make_cache:
            pad = ctx.make_cache - k.shape[1]
            nc = {
                "k": constrain(jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))), *KV_AXES),
                "v": constrain(jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))), *KV_AXES),
            }
        return out, nc
    out, ck, cv = attention_cached(
        cfg, p, h, cache["k"], cache["v"], ctx.row_idx, ctx.positions, ctx.attn_mask,
        row_start=ctx.row_start,
    )
    return out, {"k": ck, "v": cv}


def apply_block(cfg, kind, p, h, ctx: Ctx, cache, shared_p, layer=None):
    """Returns (h', the block's new cache, the routed experts that live rows
    reached: an int32 scalar for a MoE block, else 0).  With ``layer``, a
    MoE block's routed experts in ``p`` are the group's whole stacks and
    ``layer`` is the block's index in them (``moe.moe_apply``)."""
    if kind in ("dense", "moe", "cross"):
        a, new_cache = _attn_dispatch(cfg, p["attn"], rms_norm(h, p["ln1"].value, cfg.norm_eps), ctx, cache, kind)
        h = h + a
        hn = rms_norm(h, p["ln2"].value, cfg.norm_eps)
        if kind == "moe":
            from repro.models.moe import moe_apply

            # a cached call's rows with no cache row are padding: they route nowhere
            live = ctx.row_idx >= 0 if ctx.mode == "cached" else None
            out, touched = moe_apply(cfg, p["mlp"], hn, live, layer)
            return h + out, new_cache, touched
        return h + _mlp_apply(cfg, p["mlp"], hn), new_cache, 0
    if kind == "mamba2":
        out, new_cache = m2.mamba2_apply(
            cfg, p["mamba"], rms_norm(h, p["ln"].value, cfg.norm_eps), cache, ctx.commit_mask
        )
        if ctx.mode == "full" and not ctx.make_cache:
            new_cache = None
        return h + out, new_cache, 0
    if kind == "rwkv6":
        tm_cache = None if cache is None else {"sx_tm": cache["sx_tm"], "wkv": cache["wkv"]}
        cm_cache = None if cache is None else {"sx_cm": cache["sx_cm"]}
        out, nc_tm = rk.rwkv6_time_mix(cfg, p["tm"], rms_norm(h, p["ln1"].value, cfg.norm_eps), tm_cache, ctx.commit_mask)
        h = h + out
        out, nc_cm = rk.rwkv6_channel_mix(cfg, p["tm"], rms_norm(h, p["ln2"].value, cfg.norm_eps), cm_cache, ctx.commit_mask)
        h = h + out
        new_cache = {**nc_tm, **nc_cm}
        if ctx.mode == "full" and not ctx.make_cache:
            new_cache = None
        return h, new_cache, 0
    if kind == "shared":
        # zamba2: weight-shared attn+mlp block on concat(h, x0)
        inp = jnp.concatenate([h, ctx.x0], axis=-1) @ p["in_w"].value
        a, new_cache = _attn_dispatch(
            cfg, shared_p["attn"], rms_norm(inp, shared_p["ln1"].value, cfg.norm_eps), ctx, cache, "dense"
        )
        inp = inp + a
        hn = rms_norm(inp, shared_p["ln2"].value, cfg.norm_eps)
        inp = inp + _mlp_apply(cfg, shared_p["mlp"], hn)
        return h + inp, new_cache, 0
    raise ValueError(kind)


# -----------------------------------------------------------------------------
# Model init / apply
# -----------------------------------------------------------------------------


def init_model(cfg, key):
    plan = build_plan(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, len(plan) + 3)
    params = {
        "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model), ("vocab", "embed"), dt, scale=1.0),
        "final_norm": ones_init((cfg.d_model,), ("act_embed",), dt),
        "lm_head": dense_init(keys[1], (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dt),
        "groups": [],
        "shared_attn": None,
    }
    if any("shared" in unit for unit, _ in plan):
        params["shared_attn"] = init_shared_attn(cfg, keys[2])
    for gi, (unit_def, n_reps) in enumerate(plan):
        gkey = keys[3 + gi]

        def init_unit(k):
            bkeys = jax.random.split(k, len(unit_def))
            return tuple(init_block(cfg, kind, bk) for kind, bk in zip(unit_def, bkeys))

        stacked = jax.vmap(init_unit)(jax.random.split(gkey, n_reps))
        params["groups"].append(add_leading_axis(stacked, "unit"))
    return params


def init_cache(cfg, B, S_max, dtype):
    plan = build_plan(cfg)
    groups = []
    for unit_def, n_reps in plan:
        unit = tuple(init_block_cache(cfg, kind, B, S_max, dtype) for kind in unit_def)
        stacked = jax.tree.map(lambda x: jnp.zeros((n_reps,) + x.shape, x.dtype), unit)
        groups.append(stacked)
    return {"len": jnp.zeros((), jnp.int32), "groups": groups}


_ROUTED = ("wg", "wu", "wd")  # a MoE block's routed-expert weights


def apply_model(cfg, params, h, ctx: Ctx, cache=None):
    """h: [B, n, d] embedded inputs. Returns (hidden [B,n,d], new_cache, the
    routed experts that live rows reached, summed over the MoE layers: an
    int32 scalar, or 0 for a model with none)."""
    flags = get_flags()
    plan = build_plan(cfg)
    ctx.x0 = h if any("shared" in u for u, _ in plan) else None
    shared_p = params["shared_attn"]
    new_groups = []
    touched = 0

    for gi, (unit_def, n_reps) in enumerate(plan):
        stacked = params["groups"][gi]
        cache_g = cache["groups"][gi] if cache is not None else None
        emit_cache = ctx.mode == "cached" or ctx.make_cache
        count = "moe" in unit_def
        # A serving forward (verify, expansion, prefill) reads the routed
        # experts' weights in place: they stay out of the scanned tree, and
        # each MoE block gets the group's whole stacks and its layer index.
        # Training scans them as the layer's slice, since a grouped matmul's
        # weight gradient over the whole stack would be L times the size.
        whole = count and emit_cache
        held = (None,) * len(unit_def)
        if whole:
            held = tuple({k: b["mlp"][k] for k in _ROUTED} if kind == "moe" else None
                         for b, kind in zip(stacked, unit_def))
            stacked = tuple({**b, "mlp": {k: v for k, v in b["mlp"].items() if k not in _ROUTED}}
                            if kind == "moe" else b for b, kind in zip(stacked, unit_def))

        def unit_fn(h_carry, xs):
            up, uc, layer = xs
            new_uc = []
            n = 0
            for bi, kind in enumerate(unit_def):
                bc = None if uc is None else uc[bi]
                bp = up[bi] if held[bi] is None else {
                    **up[bi], "mlp": {**up[bi]["mlp"], **held[bi]}}
                h_carry, nc, nb = apply_block(cfg, kind, bp, h_carry, ctx, bc, shared_p, layer)
                new_uc.append(nc)
                n = n + nb
            ys = tuple(new_uc) if emit_cache else None
            if count:  # (caches, experts reached by this unit's MoE blocks)
                ys = (ys, n)
            return h_carry, ys

        if flags.seq_shard_acts:
            # sequence parallelism: the residual stream carried between units
            # (and saved by remat) shards over "model", bounding per-device
            # activation memory at production sequence lengths.
            inner_fn = unit_fn

            def unit_fn(h_carry, xs):  # noqa: F811
                h_carry = constrain(h_carry, "batch", "act_seq", None)
                h_out, ys = inner_fn(h_carry, xs)
                return constrain(h_out, "batch", "act_seq", None), ys

        if flags.remat == "full":
            unit_fn = jax.checkpoint(unit_fn)

        if flags.scan_layers and n_reps > 1:
            layers = jnp.arange(n_reps, dtype=jnp.int32) if whole else None
            h, ys = jax.lax.scan(unit_fn, h, (stacked, cache_g, layers))
            if count:
                ys, n = ys
                touched = touched + jnp.sum(n)
            new_groups.append(ys)
        else:
            ys = []
            for r in range(n_reps):
                up = jax.tree.map(
                    lambda p, _r=r: Param(p.value[_r], p.axes[1:]),
                    stacked,
                    is_leaf=lambda x: isinstance(x, Param),
                )
                uc = None if cache_g is None else jax.tree.map(lambda x, _r=r: x[_r], cache_g)
                h, nc = unit_fn(h, (up, uc, r if whole else None))
                if count:
                    nc, n = nc
                    touched = touched + n
                ys.append(nc)
            if emit_cache:
                new_groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *ys))
            else:
                new_groups.append(None)

    h = rms_norm(h, params["final_norm"].value, cfg.norm_eps)
    if cache is not None or ctx.make_cache:
        return h, {"len": None, "groups": new_groups}, touched  # len managed by caller
    return h, None, touched


def axes_tree(stacked):
    return jax.tree.map(lambda p: p.axes, stacked, is_leaf=lambda x: isinstance(x, Param))


def logits_from_hidden(cfg, params, h):
    # float32 logits whatever the compute dtype: a bf16 round of the logits
    # would tie the top two in a large share of greedy steps
    logits = jnp.einsum("bnd,dv->bnv", h, params["lm_head"].value,
                        preferred_element_type=jnp.float32)
    return constrain(logits, "batch", "seq", "vocab")


def embed_tokens(cfg, params, tokens):
    emb = params["embed"].value[tokens]
    return constrain(emb, "batch", "seq", "act_embed")
