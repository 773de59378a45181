"""Plain float32 reference of a DeepSeek-MoE decoder (arXiv:2401.06066), for
the harness's tests; found as ``bench/reference/dsmoe.py`` in a test's
benchmark root, beside ``bench/layouts/dsmoe.py``.

Pre-norm blocks: RMSNorm, multi-head attention with rotary embeddings
(rotate-half form), RMSNorm, then a SwiGLU MLP in the first
``first_k_dense_replace`` layers, and in the others the sum of the shared
experts' SwiGLU (one MLP of ``n_shared_experts`` times the expert width) and
the routed experts': softmax gates over all experts, the top
``num_experts_per_tok`` kept and renormalised to sum to one, each token's
experts weighted by them.  Every expert is evaluated for every token and
the unused ones weighted by zero: no capacity, no dropped token.  Float32,
every product at ``Precision.HIGHEST``; imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, w, eps, theta):
    B, T, _ = h.shape
    x = _rms_norm(h, w["ln1"], eps)
    q = _rope(jnp.einsum("btd,dhk->bthk", x, w["wq"], precision=HIGHEST), theta)
    k = _rope(jnp.einsum("btd,dhk->bthk", x, w["wk"], precision=HIGHEST), theta)
    v = jnp.einsum("btd,dhk->bthk", x, w["wv"], precision=HIGHEST)
    hq, hkv, hd = q.shape[2], k.shape[2], q.shape[3]
    qg = q.reshape(B, T, hkv, hq // hkv, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k, precision=HIGHEST) / jnp.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgts,bskh->btkgh", p, v, precision=HIGHEST).reshape(B, T, hq, hd)
    return h + jnp.einsum("bthk,hkd->btd", o, w["wo"], precision=HIGHEST)


def _swiglu(x, wg, wu, wd):
    g = jnp.einsum("...d,df->...f", x, wg, precision=HIGHEST)
    u = jnp.einsum("...d,df->...f", x, wu, precision=HIGHEST)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, wd, precision=HIGHEST)


def _take(layers, i):
    return {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False).astype(jnp.float32)
            for k, v in layers.items()}


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _dense_layer(h, layers, i, *, eps, theta):
    w = _take(layers, i)
    h = _attention(h, w, eps, theta)
    return h + _swiglu(_rms_norm(h, w["ln2"], eps), w["wg"], w["wu"], w["wd"])


@functools.partial(jax.jit, static_argnames=("eps", "theta", "top_k"))
def _moe_layer(h, layers, i, *, eps, theta, top_k):
    w = _take(layers, i)
    h = _attention(h, w, eps, theta)
    x = _rms_norm(h, w["ln2"], eps)
    gates = jax.nn.softmax(jnp.einsum("btd,de->bte", x, w["router"], precision=HIGHEST), -1)
    topv, topi = jax.lax.top_k(gates, top_k)
    topv = topv / jnp.sum(topv, -1, keepdims=True)
    E = gates.shape[-1]
    weight = jnp.sum(jax.nn.one_hot(topi, E) * topv[..., None], axis=-2)  # [B, T, E]
    g = jnp.einsum("btd,edf->btef", x, w["ewg"], precision=HIGHEST)
    u = jnp.einsum("btd,edf->btef", x, w["ewu"], precision=HIGHEST)
    y = jnp.einsum("btef,efd->bted", jax.nn.silu(g) * u, w["ewd"], precision=HIGHEST)
    routed = jnp.einsum("bte,bted->btd", weight, y, precision=HIGHEST)
    return h + routed + _swiglu(x, w["swg"], w["swu"], w["swd"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_gaps(h, final_norm, lm_head, nxt, *, eps):
    x = _rms_norm(h, final_norm.astype(jnp.float32), eps)
    logits = jnp.einsum("btd,dv->btv", x, lm_head.astype(jnp.float32), precision=HIGHEST)
    best = jnp.max(logits, axis=-1)
    pick = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    return best - pick, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def hidden(weights: dict, cfg: dict, tokens, *, control: bool = False):
    if control:
        raise NotImplementedError("this reference has no control")
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    h = weights["embed"][tokens].astype(jnp.float32)
    L = weights["layers"]
    for i in range(cfg["first_k_dense_replace"]):
        h = _dense_layer(h, L["dense"], jnp.int32(i), eps=eps, theta=theta)
    for i in range(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]):
        h = _moe_layer(h, L["moe"], jnp.int32(i), eps=eps, theta=theta,
                       top_k=int(cfg["num_experts_per_tok"]))
    return h


def head(h, weights: dict, cfg: dict, nxt, *, control: bool = False):
    if control:
        raise NotImplementedError("this reference has no control")
    return _head_gaps(h, weights["final_norm"], weights["lm_head"], nxt,
                      eps=float(cfg["rms_norm_eps"]))
