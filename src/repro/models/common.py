"""Shared model building blocks: init helpers, RMSNorm, RoPE, embeddings."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding import Param


def dense_init(key, shape, axes, dtype, scale=None):
    """Truncated-normal init boxed with logical axes."""
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    std = scale if scale is not None else fan_in**-0.5
    val = std * jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
    return Param(val.astype(dtype), tuple(axes))


def zeros_init(shape, axes, dtype):
    return Param(jnp.zeros(shape, dtype), tuple(axes))


def ones_init(shape, axes, dtype):
    return Param(jnp.ones(shape, dtype), tuple(axes))


def rms_norm(x, weight, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


def rope_frequencies(head_dim: int, theta: float, yarn=None):
    """theta^(-2i/head_dim) for i < head_dim/2; with ``yarn``
    (``configs.base.YarnScaling``) each frequency past the correction
    range's low end is mixed linearly towards its value over the factor,
    wholly so past the high end."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if yarn is None:
        return freqs
    low, high = yarn.correction_range(head_dim, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x, positions, theta: float, yarn=None):
    """Rotary embedding. x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta, yarn)  # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None and yarn.cos_sin_factor != 1.0:
        cos, sin = cos * yarn.cos_sin_factor, sin * yarn.cos_sin_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down, b_gate=None, b_up=None):
    """SwiGLU(x) = (silu(x W_g + b_g) * (x W_u + b_u)) W_d.

    This is the op the ``fused_swiglu`` Pallas kernel implements in one HBM
    pass (paper §3.3); here in composable jnp form for XLA fusion.
    """
    g = x @ w_gate
    u = x @ w_up
    if b_gate is not None:
        g = g + b_gate
    if b_up is not None:
        u = u + b_up
    return (jax.nn.silu(g) * u) @ w_down


def cross_entropy_loss(logits, labels, mask=None):
    """Mean token CE. logits: [..., vocab] (may be vocab-sharded under pjit)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
