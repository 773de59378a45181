"""Plain float32 reference of a DeepSeek-V2 decoder (arXiv:2405.04434; the
published ``modeling_deepseek.py`` forward of DeepSeek-V2-Lite).

Pre-norm blocks.  Attention is multi-head latent attention, materialised:
  q       = x @ wq                             [T, H, nope + rope]  (no query
            compression: ``q_lora_rank`` null)
  c, k_pe = split(x @ wdkv)                    c: [T, kv_lora_rank], k_pe: [T, rope]
  c       = RMSNorm(c, kv_norm)
  k_nope  = c @ wuk, v = c @ wuv               per head
  scores  = (q_nope . k_nope + rope(q_pe) . rope(k_pe)) * scale, causal
  out     = softmax(scores) v @ wo
with ``scale = (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2`` and
YaRN rotary frequencies: theta^(-2i/rope) for i below the correction range
(``beta_fast``, ``beta_slow`` over ``original_max_position_embeddings``),
the same over ``factor`` above it, mixed linearly between; cos and sin scaled
by mscale(factor, mscale) / mscale(factor, mscale_all_dim).
The first ``first_k_dense_replace`` layers end in a SwiGLU MLP; the others
in the shared experts' SwiGLU (one MLP of ``n_shared_experts`` times the
expert width) plus the routed experts': softmax gates over all
``n_routed_experts`` in float32, the top ``num_experts_per_tok`` kept
(greedy, one group), renormalised only if ``norm_topk_prob``, times
``routed_scaling_factor``.  Every expert is evaluated for every token, one
expert at a time so that it fits beside the weights, and the unused ones
weighted by zero: no capacity, no dropped token.  Then a final RMSNorm and
an untied output head.

Departures from the published code, none of which changes the model:
  - the published code stores each rotary pair interleaved (dims 2i, 2i+1)
    and permutes q_pe and k_pe to the rotate-half order before the
    rotation.  Here the rotation is applied in rotate-half order directly;
    on the benchmark's random weights the published permutation is a fixed
    permutation of the rope columns of ``wq`` and ``wdkv``, i.e. the same
    distribution of models;
  - the latent key and value up-projections are the two halves of the
    published ``kv_b_proj`` (``wuk``, ``wuv``), the rope part of ``wq`` and
    ``wdkv`` their last ``qk_rope_head_dim`` columns, as published.

Float32, every product at ``Precision.HIGHEST`` (a TPU otherwise multiplies
float32 in bfloat16), layer by layer, one jitted program per kind of layer;
imports nothing of the program under test and reads only the weights the
benchmark made (``bench/layouts/deepseek_v2.py``).

``control=True`` is the same forward one precision down from the bfloat16
the configuration states, in weights and activations alike (a float8 W8A8
deployment): every matrix weight rounded to float8 (e4m3) with one scale per
output channel, and every activation that enters a product with a weight
rounded to float8 with one scale per token; the rest (attention scores and
weighted sums, norms, gates, residual stream) in bfloat16.  The correctness
check must fail it.  Float8 weights alone (activations in bfloat16, the
Llama reference's control) do not fail it by a clear margin here: a bfloat16
program already turns some near-tied choices of the sixth expert, each of
which moves a layer's output by that expert's whole gated share, and the
program's widest gap comes from those turns (PERF.md section 2).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(w, reduce_axes):
    """Round ``w`` to float8 e4m3 with one scale per output channel (the
    axes not in ``reduce_axes``), back in bfloat16."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale).astype(jnp.bfloat16)


# each matrix's input axes (reduced over), per weight name: float8 scales run
# over the others.  The routed experts' (``ewg``, ``ewu``, ``ewd``: one [d, f]
# or [f, d] matrix per expert) are rounded one expert at a time.
_IN_AXES = {"wq": (0,), "wdkv": (0,), "wuk": (0,), "wuv": (0,), "wo": (0, 1), "wg": (0,),
            "wu": (0,), "wd": (0,), "router": (0,), "swg": (0,), "swu": (0,), "swd": (0,)}
_EXPERTS = ("ewg", "ewu", "ewd")


def _q8(x, control):
    """``x``, an activation about to meet a weight in a product over its last
    axis; under the control rounded to float8 e4m3 with one scale per row."""
    if not control:
        return x
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return ((x32 / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale).astype(x.dtype)


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(rope: dict, dim: int, theta: float):
    """(inverse frequencies [dim/2], cos/sin factor, softmax factor)."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not rope:
        return inv, 1.0, 1.0
    factor, orig = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def corr_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))) * ramp \
        + inv * (1.0 - ramp)
    m_all = float(rope.get("mscale_all_dim", 0) or 0)
    cos_sin = _mscale(factor, float(rope.get("mscale", 1))) / _mscale(factor, m_all)
    softmax = _mscale(factor, m_all) ** 2 if m_all else 1.0
    return inv, cos_sin, softmax


def _rope(x, inv, cos_sin):
    """x [B, T, ..., dim] at positions 0..T-1, rotate-half order."""
    T, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    shape = (1, T) + (1,) * (x.ndim - 3) + (half,)
    cos = (jnp.cos(ang) * cos_sin).reshape(shape)
    sin = (jnp.sin(ang) * cos_sin).reshape(shape)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _mat(v, in_axes, control):
    return _fp8(v, in_axes) if control else v.astype(jnp.float32)


def _mats(w, control):
    """The layer's weights but the routed experts' in the forward's precision
    (the norm scales as they are under the control), its activation dtype
    and matmul precision."""
    if control:
        return ({k: _fp8(v, _IN_AXES[k]) if k in _IN_AXES else v for k, v in w.items()},
                jnp.bfloat16, None)
    return {k: v.astype(jnp.float32) for k, v in w.items()}, jnp.float32, HIGHEST


def _attention(h, w, a, *, eps, nope, inv, cos_sin, scale, prec, control):
    B, T, _ = h.shape
    x = _q8(_rms_norm(h, w["ln1"], eps), control)
    q = jnp.einsum("btd,dhk->bthk", x, w["wq"], precision=prec)
    kv = jnp.einsum("btd,dl->btl", x, w["wdkv"], precision=prec)
    kvl = w["wuk"].shape[0]
    c = _q8(_rms_norm(kv[..., :kvl], w["kv_norm"], eps), control)
    k_pe = _rope(kv[..., kvl:], inv, cos_sin)  # [B, T, rope], one for every head
    q_pe = _rope(q[..., nope:], inv, cos_sin)
    k_nope = jnp.einsum("btl,lhk->bthk", c, w["wuk"], precision=prec)
    v = jnp.einsum("btl,lhk->bthk", c, w["wuv"], precision=prec)
    s = (jnp.einsum("bthk,bshk->bhts", q[..., :nope], k_nope, precision=prec)
         + jnp.einsum("bthk,bsk->bhts", q_pe, k_pe, precision=prec)).astype(jnp.float32) * scale
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]  # [t, s]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1).astype(a)
    o = jnp.einsum("bhts,bshk->bthk", p, v, precision=prec)
    o = _q8(o.reshape(B, T, -1), control).reshape(o.shape)  # wo reads every head's value
    return h + jnp.einsum("bthk,hkd->btd", o, w["wo"], precision=prec)


def _swiglu(x, wg, wu, wd, prec, control):
    x = _q8(x, control)
    g = jnp.einsum("...d,df->...f", x, wg, precision=prec)
    u = jnp.einsum("...d,df->...f", x, wu, precision=prec)
    return jnp.einsum("...f,fd->...d", _q8(jax.nn.silu(g) * u, control), wd, precision=prec)


def _take(layers, i):
    """Layer ``i``'s weights but the routed experts'."""
    return {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False) for k, v in layers.items()
            if k not in _EXPERTS}


_STATIC = ("eps", "nope", "rope_cfg", "rope_dim", "theta", "control")


def _consts(rope_cfg, rope_dim, theta, nope):
    rope = dict(rope_cfg) if rope_cfg else {}
    inv, cos_sin, softmax = _yarn(rope, rope_dim, theta)
    return inv, cos_sin, (nope + rope_dim) ** -0.5 * softmax


@functools.partial(jax.jit, static_argnames=_STATIC)
def _dense_layer(h, layers, i, *, eps, nope, rope_cfg, rope_dim, theta, control):
    w, a, prec = _mats(_take(layers, i), control)
    inv, cos_sin, scale = _consts(rope_cfg, rope_dim, theta, nope)
    h = _attention(h.astype(a), w, a, eps=eps, nope=nope, inv=inv, cos_sin=cos_sin,
                   scale=scale, prec=prec, control=control)
    return h + _swiglu(_rms_norm(h, w["ln2"], eps), w["wg"], w["wu"], w["wd"], prec, control)


@functools.partial(jax.jit, static_argnames=_STATIC + ("top_k", "norm_topk", "scaling"))
def _moe_layer(h, layers, i, *, eps, nope, rope_cfg, rope_dim, theta, control, top_k,
               norm_topk, scaling):
    w, a, prec = _mats(_take(layers, i), control)
    inv, cos_sin, scale = _consts(rope_cfg, rope_dim, theta, nope)
    h = _attention(h.astype(a), w, a, eps=eps, nope=nope, inv=inv, cos_sin=cos_sin,
                   scale=scale, prec=prec, control=control)
    x = _rms_norm(h, w["ln2"], eps)
    logits = jnp.einsum("btd,de->bte", _q8(x, control), w["router"], precision=prec)
    gates = jax.nn.softmax(logits.astype(jnp.float32), -1)
    topv, topi = jax.lax.top_k(gates, top_k)
    if norm_topk:
        topv = topv / jnp.sum(topv, -1, keepdims=True)
    E = gates.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(topi, E) * topv[..., None], axis=-2) * scaling  # [B, T, E]

    def one_expert(e, acc):
        wg, wu, wd = (_mat(layers[k][i, e], (0,), control) for k in _EXPERTS)
        y = _swiglu(x, wg, wu, wd, prec, control)
        return acc + weight[..., e, None] * y.astype(jnp.float32)

    routed = jax.lax.fori_loop(0, E, one_expert, jnp.zeros(h.shape, jnp.float32))
    return h + (routed + _swiglu(x, w["swg"], w["swu"], w["swd"], prec, control)).astype(a)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head_gaps(h, final_norm, lm_head, nxt, *, eps, control):
    """Per position: the reference's best logit minus the logit of ``nxt``
    (the token the program served there), and the head's own top token."""
    x = _rms_norm(h, final_norm, eps)
    if control:
        x = _q8(x.astype(jnp.bfloat16), True)
        logits = jnp.einsum("btd,dv->btv", x, _fp8(lm_head, (0,)),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32), lm_head.astype(jnp.float32),
                            precision=HIGHEST)
    best = jnp.max(logits, axis=-1)
    pick = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    return best - pick, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def hidden(weights: dict, cfg: dict, tokens, *, control: bool = False):
    """Final residual stream [B, T, d] of ``tokens`` [B, T]."""
    if cfg.get("scoring_func", "softmax") != "softmax" or cfg.get("n_group", 1) != 1:
        raise ValueError("this reference routes by softmax over one group")
    rope = cfg.get("rope_scaling") or {}
    if rope and rope.get("type", rope.get("rope_type")) != "yarn":
        raise ValueError(f"this reference has YaRN or no rotary scaling, not {rope}")
    common = dict(eps=float(cfg["rms_norm_eps"]), nope=int(cfg["qk_nope_head_dim"]),
                  rope_cfg=tuple(sorted(rope.items())), rope_dim=int(cfg["qk_rope_head_dim"]),
                  theta=float(cfg["rope_theta"]), control=control)
    h = weights["embed"][tokens].astype(jnp.bfloat16 if control else jnp.float32)
    L, k = weights["layers"], int(cfg["first_k_dense_replace"])
    for i in range(k):
        h = _dense_layer(h, L["dense"], jnp.int32(i), **common)
    for i in range(cfg["num_hidden_layers"] - k):
        h = _moe_layer(h, L["moe"], jnp.int32(i), top_k=int(cfg["num_experts_per_tok"]),
                       norm_topk=bool(cfg["norm_topk_prob"]),
                       scaling=float(cfg.get("routed_scaling_factor", 1.0)), **common)
    return h


def head(h, weights: dict, cfg: dict, nxt, *, control: bool = False):
    """(gap [B, T], top token [B, T]) of the output head on ``h``: the head's
    best logit minus its logit of ``nxt``, and the token it puts first."""
    return _head_gaps(h, weights["final_norm"], weights["lm_head"], nxt,
                      eps=float(cfg["rms_norm_eps"]), control=control)
