"""Mixture-of-Experts: capacity-based grouped dispatch, jittable & shardable.

Two execution strategies (flags.moe_impl):
  "tp" — TP-within-expert (default): expert weights replicated across "model"
         on the expert dim, sharded on the ff dim.  Dispatch is local to each
         data shard; the only collective is the same psum a dense MLP needs.
  "ep" — expert-parallel: experts sharded across "model"; each model shard
         computes the full-ff MLP of its own experts for the (replicated)
         local tokens and a psum combines contributions.  Evaluated against
         "tp" in the §Perf hillclimb.

Dispatch is the sort-based capacity scheme: (token, k) pairs are sorted by
expert id, positions-within-expert beyond capacity drop (weighted renorm keeps
the estimator unbiased enough for routing studies; capacity_factor controls
drops).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.flags import get_flags
from repro.models.common import dense_init
from repro.sharding import get_mesh


def init_moe(cfg, key):
    E = cfg.n_experts
    dff = cfg.moe_d_ff or cfg.d_ff
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "router": dense_init(ks[0], (d, E), ("embed", None), dt),
        "wg": dense_init(ks[1], (E, d, dff), ("experts", "embed", "ff"), dt),
        "wu": dense_init(ks[2], (E, d, dff), ("experts", "embed", "ff"), dt),
        "wd": dense_init(ks[3], (E, dff, d), ("experts", "ff", "embed"), dt),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * dff
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": dense_init(ks2[0], (d, sff), ("embed", "ff"), dt),
            "wu": dense_init(ks2[1], (d, sff), ("embed", "ff"), dt),
            "wd": dense_init(ks2[2], (sff, d), ("ff", "embed"), dt),
        }
    return p


def _dispatch(x2d, router_w, n_experts, top_k, capacity):
    """Route tokens to per-expert slots. Returns (xbuf [E,C,d], combine info)."""
    T, d = x2d.shape
    gates = jax.nn.softmax((x2d.astype(jnp.float32)) @ router_w.astype(jnp.float32))
    topv, topi = jax.lax.top_k(gates, top_k)  # [T,k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    flat_e = topi.reshape(-1)  # [T*k]
    sort_idx = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[sort_idx]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(n_experts))
    pos_in_e = jnp.arange(T * top_k) - seg_start[sorted_e]
    keep = pos_in_e < capacity
    dest = jnp.where(keep, sorted_e * capacity + pos_in_e, n_experts * capacity)
    tok = sort_idx // top_k

    xbuf = jnp.zeros((n_experts * capacity + 1, d), x2d.dtype).at[dest].add(x2d[tok])
    w_sorted = topv.reshape(-1)[sort_idx] * keep
    return xbuf[:-1].reshape(n_experts, capacity, d), (dest, tok, w_sorted)


def _combine(h, info, T):
    dest, tok, w_sorted = info
    E_C, d = h.reshape(-1, h.shape[-1]).shape
    hflat = jnp.concatenate([h.reshape(E_C, d), jnp.zeros((1, d), h.dtype)], 0)
    contrib = hflat[dest] * w_sorted[:, None].astype(h.dtype)
    return jnp.zeros((T, d), h.dtype).at[tok].add(contrib)


def _expert_mlp(xbuf, wg, wu, wd):
    g = jnp.einsum("ecd,edf->ecf", xbuf, wg)
    u = jnp.einsum("ecd,edf->ecf", xbuf, wu)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)


def _moe_local(x2d, p_vals, cfg, capacity):
    xbuf, info = _dispatch(x2d, p_vals["router"], cfg.n_experts, cfg.moe_top_k, capacity)
    h = _expert_mlp(xbuf, p_vals["wg"], p_vals["wu"], p_vals["wd"])
    return _combine(h, info, x2d.shape[0])


def moe_apply(cfg, p, x):
    """x: [B, S, d] (or [B, n, d]); returns same shape."""
    flags = get_flags()
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    mesh = get_mesh()
    p_vals = {k: v.value for k, v in p.items() if k != "shared"}

    if mesh is None or "model" not in mesh.axis_names:
        T = x2d.shape[0]
        cap = max(1, math.ceil(T * cfg.moe_top_k / cfg.n_experts * cfg.capacity_factor))
        out = _moe_local(x2d, p_vals, cfg, cap)
    else:
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dsize = math.prod(mesh.shape[a] for a in data_axes) if data_axes else 1
        msize = mesh.shape["model"]
        T_local = max(1, (B * S) // max(dsize, 1))
        cap = max(1, math.ceil(T_local * cfg.moe_top_k / cfg.n_experts * cfg.capacity_factor))
        tok_spec = P(data_axes if data_axes else None, None)

        if flags.moe_impl == "ep" and cfg.n_experts % msize == 0:
            # expert-parallel: shard experts over "model"; tokens replicated on
            # "model"; each shard computes its experts' full-ff MLP; psum merges.
            e_loc = cfg.n_experts // msize

            def ep_block(x_loc, router, wg, wu, wd):
                midx = jax.lax.axis_index("model")
                gates = jax.nn.softmax(x_loc.astype(jnp.float32) @ router.astype(jnp.float32))
                topv, topi = jax.lax.top_k(gates, cfg.moe_top_k)
                topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
                # local expert ids owned by this shard: [midx*e_loc, (midx+1)*e_loc)
                rel = topi - midx * e_loc  # [T,k]
                mine = (rel >= 0) & (rel < e_loc)
                flat_e = jnp.where(mine, rel, e_loc).reshape(-1)
                sort_idx = jnp.argsort(flat_e)
                sorted_e = flat_e[sort_idx]
                seg_start = jnp.searchsorted(sorted_e, jnp.arange(e_loc))
                cap_ep = max(1, math.ceil(T_local * cfg.moe_top_k / cfg.n_experts * cfg.capacity_factor))
                pos_in_e = jnp.arange(flat_e.shape[0]) - seg_start[sorted_e.clip(0, e_loc - 1)]
                keep = (sorted_e < e_loc) & (pos_in_e < cap_ep)
                dest = jnp.where(keep, sorted_e * cap_ep + pos_in_e, e_loc * cap_ep)
                tok = sort_idx // cfg.moe_top_k
                xbuf = jnp.zeros((e_loc * cap_ep + 1, d), x_loc.dtype).at[dest].add(x_loc[tok])
                h = _expert_mlp(xbuf[:-1].reshape(e_loc, cap_ep, d), wg, wu, wd)
                w_sorted = (topv.reshape(-1)[sort_idx] * keep).astype(h.dtype)
                y = _combine(h, (dest, tok, w_sorted), x_loc.shape[0])
                return jax.lax.psum(y, "model")

            out = jax.shard_map(
                ep_block,
                mesh=mesh,
                in_specs=(tok_spec, P(None, None), P("model", None, None), P("model", None, None), P("model", None, None)),
                out_specs=tok_spec,
                check_vma=False,
            )(x2d, p_vals["router"], p_vals["wg"], p_vals["wu"], p_vals["wd"])
        else:
            # TP-within-expert: ff dim sharded over "model"; dispatch local.
            def tp_block(x_loc, router, wg, wu, wd):
                y = _moe_local(x_loc, {"router": router, "wg": wg, "wu": wu, "wd": wd}, cfg, cap)
                return jax.lax.psum(y, "model")

            out = jax.shard_map(
                tp_block,
                mesh=mesh,
                in_specs=(tok_spec, P(None, None), P(None, None, "model"), P(None, None, "model"), P(None, "model", None)),
                out_specs=tok_spec,
                check_vma=False,
            )(x2d, p_vals["router"], p_vals["wg"], p_vals["wu"], p_vals["wd"])

    if cfg.n_shared_experts:
        sh = p["shared"]
        g = x2d @ sh["wg"].value
        u = x2d @ sh["wu"].value
        out = out + (jax.nn.silu(g) * u) @ sh["wd"].value

    return out.reshape(B, S, d)
