"""Latency-optimized Pallas kernels (paper §3.3), TPU-adapted.

  tree_attention    — non-square tree-mask attention (draft + verify + decode)
  decode_attention  — split-KV decode, in-kernel combine (1 launch, 0 barriers)
  fused_swiglu      — silu(xW) ⊙ (xV) in one HBM pass over x
  int4_matmul       — AWQ groupwise int4 dequant-GEMM

``ops`` holds the jit'd public wrappers; ``ref`` the pure-jnp oracles the
tests sweep against.

Every kernel runs in interpret mode on the CPU backend and compiles to
Mosaic everywhere else: ``interpret=None`` (the default throughout) follows
``jax.default_backend()`` at trace time, so no TPU path can fall back to the
Pallas interpreter by accident.
"""

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit bool wins,
    ``None`` means interpret exactly when the default backend is the CPU."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
