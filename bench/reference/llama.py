"""Plain float32 reference of a Llama-architecture decoder (DeepSeek-Coder).

Pre-norm blocks: RMSNorm, grouped-query attention with rotary embeddings
(rotate-half form, frequencies theta^(-2i/hd)), RMSNorm, SwiGLU MLP, then a
final RMSNorm and an untied output head.  The configuration file states the
numbers as run: ``rope_theta``, ``rms_norm_eps``, and no rotary scaling.

Written from the published description in straightforward ``jax.numpy``; it
imports nothing of the program under test and takes only the weights the
benchmark made (``bench/weights.py`` layout).  Float32 throughout, with every
matrix product at ``Precision.HIGHEST`` (a TPU otherwise multiplies float32
in bfloat16).  It runs layer by layer, one jitted layer program for all
layers, on a batch of right-padded sequences under a causal mask, so padding
never reaches a real position.

``control=True`` is the same forward one precision down from the bfloat16
the configuration states: every matrix weight rounded to float8 (e4m3) with
one scale per output channel, activations in bfloat16.  The correctness check
must fail it.
"""

from __future__ import annotations

import functools

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


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [B, T, H, hd] at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]  # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def _layer(h, layers, i, *, eps, theta, control):
    """One decoder block on h [B, T, d]; layer ``i`` of the stacked weights."""
    w = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False) for k, v in layers.items()}
    if control:
        act = jnp.bfloat16
        prec = None
        mats = {"wq": _fp8(w["wq"], (0,)), "wk": _fp8(w["wk"], (0,)), "wv": _fp8(w["wv"], (0,)),
                "wo": _fp8(w["wo"], (0, 1)), "wg": _fp8(w["wg"], (0,)),
                "wu": _fp8(w["wu"], (0,)), "wd": _fp8(w["wd"], (0,))}
    else:
        act = jnp.float32
        prec = HIGHEST
        mats = {k: w[k].astype(jnp.float32) for k in ("wq", "wk", "wv", "wo", "wg", "wu", "wd")}
    h = h.astype(act)
    B, T, _ = h.shape
    x = _rms_norm(h, w["ln1"], eps)
    q = _rope(jnp.einsum("btd,dhk->bthk", x, mats["wq"], precision=prec), theta)
    k = _rope(jnp.einsum("btd,dhk->bthk", x, mats["wk"], precision=prec), theta)
    v = jnp.einsum("btd,dhk->bthk", x, mats["wv"], precision=prec)
    hq, hkv, hd = q.shape[2], k.shape[2], q.shape[3]
    qg = q.reshape(B, T, hkv, hq // hkv, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k, precision=prec).astype(jnp.float32) / jnp.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]  # [t, s]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1).astype(act)
    o = jnp.einsum("bkgts,bskh->btkgh", p, v, precision=prec).reshape(B, T, hq, hd)
    h = h + jnp.einsum("bthk,hkd->btd", o, mats["wo"], precision=prec)
    x = _rms_norm(h, w["ln2"], eps)
    g = jnp.einsum("btd,df->btf", x, mats["wg"], precision=prec)
    u = jnp.einsum("btd,df->btf", x, mats["wu"], precision=prec)
    return h + jnp.einsum("btf,fd->btd", jax.nn.silu(g) * u, mats["wd"], precision=prec)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head_gaps(h, final_norm, lm_head, nxt, *, eps, control):
    """Per position: the reference's best logit minus the logit of ``nxt``
    (the token the program served there), and the head's own top token."""
    x = _rms_norm(h, final_norm, eps)
    if control:
        logits = jnp.einsum("btd,dv->btv", x.astype(jnp.bfloat16), _fp8(lm_head, (0,)),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32), lm_head.astype(jnp.float32),
                            precision=HIGHEST)
    best = jnp.max(logits, axis=-1)
    pick = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
    return best - pick, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def hidden(weights: dict, cfg: dict, tokens, *, control: bool = False):
    """Final residual stream [B, T, d] of ``tokens`` [B, T]."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    h = weights["embed"][tokens].astype(jnp.bfloat16 if control else jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, weights["layers"], jnp.int32(i), eps=eps, theta=theta, control=control)
    return h


def head(h, weights: dict, cfg: dict, nxt, *, control: bool = False):
    """(gap [B, T], top token [B, T]) of the output head on ``h``: the head's
    best logit minus its logit of ``nxt``, and the token it puts first."""
    return _head_gaps(h, weights["final_norm"], weights["lm_head"], nxt,
                      eps=float(cfg["rms_norm_eps"]), control=control)
