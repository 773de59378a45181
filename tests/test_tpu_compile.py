"""Mosaic compiles of the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers and compiles one kernel at the real widths of
the DeepSeek-Coder-33B target / 1.3B draft pair (and DeepSeek-V2-Lite's
expert layer, whose grouped matmuls ``jax.lax.ragged_dot`` lowers to a
kernel) in bf16 for a v5e chip that is described, not attached, and asserts that the compiled program holds the
kernel as a ``tpu_custom_call``.  This catches what interpret mode cannot —
block shapes off the (8, 128) tiling, DMA slices of tiled dims, VMEM
overuse — at no chip time.  The MoE target's whole verify program is
compiled too, and its temporaries show that the grouped matmuls read the
expert stacks in place.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU compiler library, and under several
pytest workers only the worker running this file may take it.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.kv_moves import kv_move_rows_pallas, slot_write_rows_pallas

TARGET = get_config("deepseek-coder-33b")
MOE = get_config("deepseek-v2-lite")
DRAFT = get_config("deepseek-coder-1.3b")
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_attention(cfg, n):
    def build(dev):
        B, S = 1, 2048
        q = _sds((B, n, cfg.n_heads, cfg.head_dim), BF16, dev)
        kv = _sds((B, S, cfg.n_kv_heads, cfg.head_dim), BF16, dev)
        mask = _sds((B, n, S), jnp.bool_, dev)
        fn = jax.jit(lambda q, k, v, m: ops.tree_attention(q, k, v, m, interpret=False))
        return fn, (q, kv, kv, mask)

    return build


def _fused_swiglu(cfg):
    def build(dev):
        x = _sds((16, cfg.d_model), BF16, dev)
        w = _sds((cfg.d_model, cfg.d_ff), BF16, dev)
        fn = jax.jit(lambda x, wg, wu: ops.fused_swiglu(x, wg, wu, interpret=False))
        return fn, (x, w, w)

    return build


def _kv_cache_leaf(dev, B=2, S=2048, U=6):
    return _sds((U, B, S, TARGET.n_kv_heads * TARGET.head_dim), BF16, dev)


def _kv_move_rows(donate):
    def build(dev):
        B, M = 2, 8
        arr = _kv_cache_leaf(dev, B=B)
        idx = _sds((B, M), jnp.int32, dev)
        fn = jax.jit(
            lambda a, s, d, act: kv_move_rows_pallas(a, s, d, act, donate=donate,
                                                     interpret=False),
            donate_argnums=(0,) if donate else ())
        return fn, (arr, idx, idx, idx)

    return build


def _slot_write_rows(dev):
    U, B, S = 6, 2, 2048
    big = _sds((U, B, S, TARGET.n_kv_heads, TARGET.head_dim), BF16, dev)
    one = _sds((U, 1, S, TARGET.n_kv_heads, TARGET.head_dim), BF16, dev)
    slot = _sds((), jnp.int32, dev)
    fn = jax.jit(
        lambda k, v, dk, dv, s: slot_write_rows_pallas([k, v], [dk, dv], s, interpret=False),
        donate_argnums=(0, 1))
    return fn, (big, big, one, one, slot)


def _moe_experts(dev):
    """One MoE layer of DeepSeek-V2-Lite on a verify's 8 tree tokens: 64
    experts of width 1408, top-6, 2 shared, read from the stacks of six
    layers at a layer index, as a serving forward reads them."""
    from repro.models.moe import moe_apply
    from repro.sharding import Param

    d, E, f, sf = MOE.d_model, MOE.n_experts, MOE.moe_d_ff, MOE.n_shared_experts * MOE.moe_d_ff
    L = 6

    def layer(x, live, router, wg, wu, wd, swg, swu, swd, at):
        box = lambda v: Param(v, ())  # noqa: E731
        p = {"router": box(router), "wg": box(wg), "wu": box(wu), "wd": box(wd),
             "shared": {"wg": box(swg), "wu": box(swu), "wd": box(swd)}}
        return moe_apply(MOE, p, x, live, at)

    args = (_sds((1, 8, d), BF16, dev), _sds((1, 8), jnp.bool_, dev), _sds((d, E), BF16, dev),
            _sds((L, E, d, f), BF16, dev), _sds((L, E, d, f), BF16, dev),
            _sds((L, E, f, d), BF16, dev), _sds((d, sf), BF16, dev), _sds((d, sf), BF16, dev),
            _sds((sf, d), BF16, dev), _sds((), jnp.int32, dev))
    return jax.jit(layer), args


def _moe_target_verify(dev):
    """DeepSeek-V2-Lite cut to 7 layers (one dense, six MoE layers scanned)
    as the benchmark's verify runs it: 8 tree rows, some of them padding
    (``row_idx`` -1, the live mask), cached mode against a 2048-row latent
    cache, under a one-chip ``model`` mesh with the serving rules."""
    from jax.sharding import Mesh

    from repro.launch.serve import serving_configs
    from repro.models.api import make_model
    from repro.sharding import SERVING_RULES, use_mesh

    cfgT, _ = serving_configs("deepseek-v2-lite", "deepseek-v2-lite-draft", smoke=False,
                              target_layers=7, dtype="bfloat16")
    m = make_model(cfgT)
    S, n = 2048, 8
    mesh = Mesh(np.array([next(iter(dev.device_set))]), ("model",))

    def on(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, dev), tree)

    with use_mesh(mesh, SERVING_RULES):
        params = on(jax.eval_shape(m.init, jax.random.PRNGKey(0)))
        cache = on(jax.eval_shape(lambda: m.init_cache(1, S, BF16)))
        rows = _sds((1, n), jnp.int32, dev)
        fn = jax.jit(lambda p, c, t, pos, r, mask: m.spec_forward(p, c, t, pos, r, mask,
                                                                  count_experts=True))
        return fn.lower(params, cache, rows, rows, rows, _sds((1, n, S), jnp.bool_, dev))


CASES = {
    # verify: 8 tree rows against a 2048-row cache, GQA group 7
    "tree_attention-33b-verify": _tree_attention(TARGET, 8),
    # the draft's fused prefix fill and first expansion: 8 fill rows + 4 leaves
    "tree_attention-1.3b-fill-grow": _tree_attention(DRAFT, 12),
    "fused_swiglu-33b-7168x19200": _fused_swiglu(TARGET),
    "fused_swiglu-1.3b-2048x5504": _fused_swiglu(DRAFT),
    "kv_move_rows-donate": _kv_move_rows(True),
    "kv_move_rows-copy-through": _kv_move_rows(False),
    "slot_write_rows": _slot_write_rows,
    "moe_experts-v2lite-verify": _moe_experts,
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, args = CASES[case](one_chip)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, f"{case}: no Mosaic kernel in the compiled program"


def test_moe_verify_reads_the_expert_stacks_in_place(one_chip):
    """The six MoE layers' grouped matmuls read the whole [L·E, d, f] stacks:
    no layer's experts are sliced out into a temporary (3 x 369 MB per layer
    when they were)."""
    compiled = _moe_target_verify(one_chip).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    assert "tpu_custom_call" in compiled.as_text()
