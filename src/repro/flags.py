"""Trace-time feature flags (kernel selection, MoE impl, remat policy).

Flags are read while tracing/jitting, so changing them re-specializes the
compiled program.  They drive the §Perf hillclimb knobs and the ablation
benchmark configurations (paper Fig. 8: kernels on/off x parallel on/off).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading


@dataclasses.dataclass
class Flags:
    use_pallas_attention: bool = False  # tree/decode attention Pallas kernels
    use_pallas_swiglu: bool = False  # fused SwiGLU kernel
    use_int4_kernel: bool = False  # AWQ dequant-GEMM kernel
    use_pallas_kv_moves: bool = False  # fused O(moved-rows) KV reorg kernels
    moe_impl: str = "tp"  # "tp" (TP-in-expert) | "ep" (expert-parallel a2a)
    remat: str = "none"  # "none" | "full"
    attn_chunk: int = 512  # q-chunk for full attention
    scan_layers: bool = True  # scan over layer stack (compile-time win)
    collective_matmul: bool = False  # ring collective-matmul decomposition
    seq_shard_acts: bool = False  # sequence parallelism: residuals + KV sharded
    #   over "model" between blocks (train/prefill memory fit at scale)
    attn_heads_tp: bool = False  # under seq_shard_acts: compute attention
    #   head-parallel (Megatron-SP): AG(k,v) + head-sharded scores instead of
    #   seq-sharded scores with per-chunk psum (§Perf collective hillclimb)


_CTX = threading.local()


def get_flags() -> Flags:
    f = getattr(_CTX, "flags", None)
    if f is None:
        f = Flags()
        _CTX.flags = f
    return f


@contextlib.contextmanager
def override_flags(**kw):
    prev = get_flags()
    cur = dataclasses.replace(prev, **kw)
    _CTX.flags = cur
    try:
        yield cur
    finally:
        _CTX.flags = prev
