"""Mixture-of-Experts: dropless grouped dispatch, jittable & shardable.

Two execution strategies (flags.moe_impl):
  "tp" — TP-within-expert (default): expert weights replicated across "model"
         on the expert dim, sharded on the ff dim.  Dispatch is local to each
         data shard; the only collective is the same psum a dense MLP needs.
  "ep" — expert-parallel: experts sharded across "model"; each model shard
         computes the full-ff MLP of its own experts for the (replicated)
         local tokens and a psum combines contributions.  Evaluated against
         "tp" in the §Perf hillclimb.

Dispatch is dropless: the (token, k) pairs are sorted by expert and each
expert's run of rows goes through one grouped matmul
(``jax.lax.ragged_dot``), so only real pairs are computed and no expert has
a capacity.  A row's result depends on that row alone, whatever else shares
the call: a speculative tree's nodes verify as they would one by one.  Rows
marked dead (``live`` false: a tree's padding slots) route nowhere and add
nothing.  The gates are a softmax over all experts, the top k kept and,
with ``cfg.norm_topk_prob``, renormalised to sum to one.

Group layout.  A serving forward (verify, expansion, prefill) hands a MoE
layer the whole group's expert stacks, ``[L, E, d, f]``, and its layer
index ``l``; the grouped matmuls read them in place as ``[L·E, d, f]``
(merging the two leading dims is a bitcast), with layer ``l``'s expert
``j`` as group ``l·E + j`` and every other layer's groups empty, so no
slice of the layer's experts is copied out (``ragged_dot`` lowers to a
kernel that cannot fuse one).  Under ``ep`` each shard holds ``E/m``
experts of every layer, ``[L, E/m, ...]``, and expert ``j`` of the shard
that starts at ``first`` is group ``l·E/m + j - first``.  Training passes
the layer's own slice ``[E, d, f]`` (group ``j``): a grouped matmul's
weight gradient has its weight operand's shape, so over the whole stack
every layer would write an ``[L·E, d, f]`` gradient.

Named scopes ``moe_dispatch``, ``moe_experts`` and ``moe_combine`` label
the three parts in the compiled program's op metadata, where a device trace
read with its HLO proto finds them.
"""

from __future__ import annotations

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


def _route(x2d, router_w, cfg):
    """Gates [T, k] and expert ids [T, k] of each token's top k."""
    gates = jax.nn.softmax(x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    topv, topi = jax.lax.top_k(gates, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return topv, topi


def _experts(x2d, topv, topi, live, wg, wu, wd, shift, lo, n):
    """The routed experts on the (token, k) pairs routed to them.

    ``wg``, ``wu`` [G, d, f] and ``wd`` [G, f, d] hold G groups; routed
    expert ``j`` is group ``j - shift``, and only pairs whose group lies in
    ``lo .. lo + n - 1`` (this layer's experts held here) are computed: the
    others (dead rows, another shard's experts) sort last and add nothing.
    Returns (their weighted sum per token [T, d] in float32, the live rows
    each group received [G]; zero outside that run)."""
    T, d = x2d.shape
    k, G = topi.shape[1], wg.shape[0]
    with jax.named_scope("moe_dispatch"):
        g = topi.reshape(-1) - shift
        mine = jnp.repeat(live, k) & (g >= lo) & (g < lo + n)
        key = jnp.where(mine, g, G)  # pairs for no group here sort last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((G + 1,), jnp.int32).at[key].add(1)[:G]
        tok = order // k
        xs = x2d[tok]
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, wg, sizes)
        u = jax.lax.ragged_dot(xs, wu, sizes)
        h = jax.lax.ragged_dot(jax.nn.silu(g) * u, wd, sizes)  # rows past the groups: 0
    with jax.named_scope("moe_combine"):
        w = jnp.where(mine, topv.reshape(-1), 0.0)[order]
        out = jnp.zeros((T, d), jnp.float32).at[tok].add(h.astype(jnp.float32) * w[:, None])
    return out, sizes


def _layer_experts(x2d, topv, topi, live, wg, wu, wd, first, layer):
    """``_experts`` over the experts ``first ..`` of one layer held here:
    ``wg``, ``wu``, ``wd`` are that layer's own [n, d, f] (``layer`` None),
    or the group's whole stacks [L, n, d, f], read in place as L·n groups
    of which groups ``layer·n ..`` are this layer's."""
    n = wg.shape[-3]
    if layer is None:
        return _experts(x2d, topv, topi, live, wg, wu, wd, first, 0, n)
    lo = layer * n

    def merged(w):
        return w.reshape((-1,) + w.shape[2:])

    return _experts(x2d, topv, topi, live, merged(wg), merged(wu), merged(wd), first - lo, lo, n)


def moe_apply(cfg, p, x, live=None, layer=None):
    """x: [B, S, d] (or [B, n, d]); ``live`` [B, S] marks the rows that are
    real tokens (None: all).  ``p``'s routed experts ``wg``, ``wu``, ``wd``
    are this layer's [E, d, f] / [E, f, d], or, with ``layer`` (its index in
    the group), the group's whole stacks [L, E, d, f] / [L, E, f, d] (see
    the module docstring).  Returns (out of x's shape, the number of routed
    experts that received at least one live row)."""
    flags = get_flags()
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    live = jnp.ones((B * S,), bool) if live is None else live.reshape(B * S)
    mesh = get_mesh()
    pv = {k: v.value for k, v in p.items() if k != "shared"}

    if mesh is None or "model" not in mesh.axis_names:
        topv, topi = _route(x2d, pv["router"], cfg)
        out, sizes = _layer_experts(x2d, topv, topi, live, pv["wg"], pv["wu"], pv["wd"], 0,
                                    layer)
        touched = jnp.sum(sizes > 0)
    else:
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        msize = mesh.shape["model"]
        tok_spec = P(data_axes if data_axes else None, None)
        live_spec = P(data_axes if data_axes else None)
        ep = flags.moe_impl == "ep" and cfg.n_experts % msize == 0

        def block(x_loc, live_loc, router, wg, wu, wd, layer=None):
            topv, topi = _route(x_loc, router, cfg)
            # expert-parallel: this shard holds experts [midx*e_loc, ...);
            # TP-within-expert: every expert, a slice of its ff columns
            first = jax.lax.axis_index("model") * wg.shape[-3] if ep else 0
            y, sizes = _layer_experts(x_loc, topv, topi, live_loc, wg, wu, wd, first, layer)
            if data_axes:
                sizes = jax.lax.psum(sizes, data_axes)
            touched = jnp.sum(sizes > 0)
            if ep:
                touched = jax.lax.psum(touched, "model")
            return jax.lax.psum(y, "model"), touched

        lead = () if layer is None else (None,)  # the stacks' layer dim
        w_specs = ((P(*lead, "model", None, None),) * 3 if ep else
                   (P(*lead, None, None, "model"), P(*lead, None, None, "model"),
                    P(*lead, None, "model", None)))
        at = () if layer is None else (jnp.asarray(layer, jnp.int32),)
        out, touched = jax.shard_map(
            block,
            mesh=mesh,
            in_specs=(tok_spec, live_spec, P(None, None)) + w_specs + (P(),) * len(at),
            out_specs=(tok_spec, P()),
            check_vma=False,
        )(x2d, live, pv["router"], pv["wg"], pv["wu"], pv["wd"], *at)

    out = out.astype(x.dtype)
    if cfg.n_shared_experts:
        sh = p["shared"]
        g = x2d @ sh["wg"].value
        u = x2d @ sh["wu"].value
        out = out + (jax.nn.silu(g) * u) @ sh["wd"].value

    return out.reshape(B, S, d), touched
