"""DeepSeek-V2-Lite's block in the program: latent attention with no query
compression, YaRN rotary scaling, dropless top-k routing with unnormalised
gates beside shared experts, a leading dense layer.

- YaRN at the published settings equals the published formula (correction
  range 10..23 of the 32 frequencies of 64 rope dims; softmax factor
  1.5896), and rotary embedding without scaling is the old computation,
  bit for bit.
- A dropless MoE call gives each row what that row gets alone; dead rows
  route nowhere; the expert count is the distinct experts of live rows.
- ``norm_topk_prob=False`` keeps the top-k softmax gates as they are.
- The serving depth cut keeps layer 0 dense, and the published model counts
  15.7 B parameters.
- Served through ``EngineSession`` the verify program counts the experts
  its tree reaches on the device, with no sync of its own.
- A serving forward (a tree verify, a prefill) reads each MoE layer's
  experts from the group's whole stack, scanned or unrolled, and gives
  what a loop over the layers gives with each layer's own slice of them:
  the same hidden states, caches and expert count."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.flags import override_flags
from repro.launch.serve import build_engine, serving_configs
from repro.models import moe
from repro.models.common import apply_rope, rms_norm, rope_frequencies
from repro.models.transformer import Ctx, apply_block, apply_model, build_plan
from repro.sharding import Param

CFG = get_config("deepseek-v2-lite")


def _published_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies, as published."""

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (low, high), freq_inter * (1 - mask) + freq_extra * mask


def test_yarn_frequencies_follow_the_published_formula():
    y = CFG.yarn
    assert y.correction_range(64, 1e4) == (10, 23)
    (low, high), want = _published_yarn_inv_freq(64, 1e4, 40, 4096, 32, 1)
    assert (low, high) == (10, 23)
    got = np.asarray(rope_frequencies(64, 1e4, y))
    # float32 rounding of two equivalent expressions (x/40 against 1/(40*b))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
    base = np.asarray(rope_frequencies(64, 1e4))
    np.testing.assert_array_equal(got[:10], base[:10])  # below low: unchanged
    np.testing.assert_allclose(got[24:], base[24:] / 40, rtol=1e-6)  # above high: / factor


def test_yarn_scales_the_softmax_not_the_rotation():
    y = CFG.yarn
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert y.softmax_factor == pytest.approx(mscale**2) == pytest.approx(1.5896, abs=1e-4)
    assert y.cos_sin_factor == 1.0  # mscale == mscale_all_dim


def _old_apply_rope(x, positions, theta):
    """apply_rope as it was before rotary scaling existed."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("theta", [1e4, 1e5])
def test_unscaled_rope_is_the_old_computation(dtype, theta):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 128)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(1030, 1039, dtype=jnp.int32), (2, 9))
    old = jax.jit(_old_apply_rope, static_argnums=2)(x, pos, theta)
    new = jax.jit(apply_rope, static_argnums=2)(x, pos, theta)
    np.testing.assert_array_equal(np.asarray(new, np.float32), np.asarray(old, np.float32))


@pytest.fixture(scope="module")
def smoke_moe():
    from repro.models.api import make_model

    cfg = get_config("deepseek-v2-lite", smoke=True)
    M = make_model(cfg)
    params = M.init(jax.random.PRNGKey(3))
    return cfg, params["groups"][1][0]["mlp"]  # the MoE group's block (stacked)


def _layer0(mlp):
    return jax.tree.map(lambda p: Param(p.value[0], p.axes[1:]), mlp,
                        is_leaf=lambda x: isinstance(x, Param))


def test_dropless_moe_gives_each_row_its_own_result(smoke_moe):
    cfg, mlp = smoke_moe
    p = _layer0(mlp)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, cfg.d_model))
    live = jnp.asarray([[True] * 9 + [False] * 3])
    out, touched = moe.moe_apply(cfg, p, x, live)
    for i in range(9):
        alone, _ = moe.moe_apply(cfg, p, x[:, i:i + 1])
        # float32; the grouped matmul may sum a row's products in another
        # order when other rows share its expert's group
        np.testing.assert_allclose(np.asarray(out[0, i]), np.asarray(alone[0, 0]),
                                   rtol=1e-5, atol=1e-5)
    # dead rows route nowhere: only the shared experts act on them
    sh = p["shared"]
    xd = x[0, 9:]
    shared = (jax.nn.silu(xd @ sh["wg"].value) * (xd @ sh["wu"].value)) @ sh["wd"].value
    np.testing.assert_allclose(np.asarray(out[0, 9:]), np.asarray(shared), rtol=1e-5, atol=1e-5)
    _, topi = moe._route(x[0, :9], p["router"].value, cfg)
    assert int(touched) == len(set(np.asarray(topi).ravel().tolist()))


def test_unnormalised_gates_are_the_top_softmax_values(smoke_moe):
    cfg, mlp = smoke_moe
    p = _layer0(mlp)
    x = jax.random.normal(jax.random.PRNGKey(5), (7, cfg.d_model))
    gates = np.asarray(jax.nn.softmax(x @ p["router"].value))
    want = -np.sort(-gates, axis=-1)[:, :cfg.moe_top_k]
    topv, _ = moe._route(x, p["router"].value, cfg)
    assert not cfg.norm_topk_prob
    np.testing.assert_allclose(np.asarray(topv), want, rtol=1e-6)
    assert (np.asarray(topv).sum(-1) < 1.0 - 1e-3).all()
    normed, _ = moe._route(x, p["router"].value, dataclasses.replace(cfg, norm_topk_prob=True))
    np.testing.assert_allclose(np.asarray(normed).sum(-1), 1.0, rtol=1e-6)


def test_serving_cut_keeps_the_dense_layer():
    cfgT, cfgD = serving_configs("deepseek-v2-lite", "deepseek-v2-lite-draft", smoke=False,
                                 target_layers=7, dtype="bfloat16")
    assert cfgT.layer_kinds == ("dense",) + ("moe",) * 6
    assert build_plan(cfgT) == [(("dense",), 1), (("moe",), 6)]
    assert cfgT.d_ff == 10944 and cfgT.moe_d_ff == 1408 and cfgT.yarn == CFG.yarn
    assert cfgD.vocab_size == cfgT.vocab_size and cfgD.norm_eps == 1e-6


def test_published_model_counts_15_7b_parameters():
    # per layer: MLA 13.76 M (direct w_q 6.29 M, w_dkv 1.18 M, kv_norm 512,
    # w_uk + w_uv 2.10 M, wo 4.19 M); dense MLP 67.24 M; each MoE layer's
    # router 0.13 M, shared 17.3 M, routed 553.6 M; embedding and head 419.4 M
    assert CFG._attn_params() == 13_763_072
    assert CFG.param_count() == 15_706_484_224
    assert round(CFG.param_count() / 1e9, 1) == 15.7


def test_direct_query_mla_has_no_query_latent():
    from repro.models.api import make_model

    shapes = jax.eval_shape(make_model(CFG).init, jax.random.PRNGKey(0))
    attn = shapes["groups"][0][0]["attn"]
    assert "w_dq" not in attn and "q_norm" not in attn
    assert attn["w_q"].value.shape == (1, 2048, 16, 192)


def test_verify_counts_experts_on_the_device():
    cfgT, cfgD = serving_configs("deepseek-v2-lite", "deepseek-v2-lite-draft")
    eng, tp, dp, _ = build_engine(cfgT, cfgD, bs=8, w=4, c=2, d=2, max_new=16, S_max=96,
                                  async_rounds=True)
    sess = eng.session(tp, dp, n_slots=1)
    sess.admit_slot(0, (np.arange(1, 13) * 7 % 256).astype(np.int32))
    for _ in range(3):
        sess.step(stats=sess.stats)
    st = sess.stats
    assert st.moe_layer_calls == 3 * eng.target.n_moe_layers == 6
    assert isinstance(st.touched, jax.Array)  # held on the device between reads
    assert 0 < st.experts_touched <= st.moe_layer_calls * cfgT.n_experts
    assert st.dispatches["jit_verify"] == 3


def test_a_dense_target_counts_no_experts(dense_pair):
    from repro.core.engine import SpecConfig, SpecEngine

    T, D, tp, dp = dense_pair
    eng = SpecEngine(T, D, SpecConfig(bs=8, w=4, c=2, d=2, max_new=8), S_max_t=64, S_max_d=64)
    out, st = eng.session(tp, dp).generate(np.arange(1, 9, dtype=np.int32).reshape(1, -1))
    assert st.rounds > 0 and st.moe_layer_calls == 0 and st.touched is None
    assert st.experts_touched == 0


def _per_layer(cfg, params, h, ctx, cache):
    """``apply_model`` as a plain loop over the layers, each MoE layer on its
    own slice of the expert stacks (``moe_apply`` with no layer index, so
    ``_experts`` takes the layer's [E, d, f] as its groups)."""
    touched, groups = 0, []
    for gi, (unit_def, n_reps) in enumerate(build_plan(cfg)):
        ys = []
        for r in range(n_reps):
            up = jax.tree.map(lambda p, _r=r: Param(p.value[_r], p.axes[1:]), params["groups"][gi],
                              is_leaf=lambda x: isinstance(x, Param))
            uc = None if cache is None else jax.tree.map(lambda x, _r=r: x[_r], cache["groups"][gi])
            new = []
            for bi, kind in enumerate(unit_def):
                h, nc, n = apply_block(cfg, kind, up[bi], h, ctx, None if uc is None else uc[bi],
                                       None)
                touched, new = touched + n, new + [nc]
            ys.append(tuple(new))
        groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *ys))
    return rms_norm(h, params["final_norm"].value, cfg.norm_eps), groups, touched


def _tree_verify_ctx(n_prompt, S):
    """A verify's 8 rows: a 6-node tree below the prompt, 2 padding rows."""
    parent = [-1, 0, 0, 1, 1, 2]
    rows = np.full((1, 8), -1, np.int32)
    pos = np.zeros((1, 8), np.int32)
    mask = np.zeros((1, 8, S), bool)
    mask[0, :, :n_prompt] = True
    for i, par in enumerate(parent):
        rows[0, i] = n_prompt + i
        if par >= 0:
            pos[0, i], mask[0, i] = pos[0, par] + 1, mask[0, par]
        else:
            pos[0, i] = n_prompt
        mask[0, i, n_prompt + i] = True
    return Ctx(mode="cached", positions=jnp.asarray(pos), row_idx=jnp.asarray(rows),
               attn_mask=jnp.asarray(mask))


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("mode", ["verify", "prefill"])
def test_serving_forward_reads_each_layers_experts_from_the_whole_stack(mode, scan_layers):
    from repro.models.api import make_model

    # three MoE layers after the dense one, so that layers sit at offsets 0, E and 2E
    cfg = dataclasses.replace(get_config("deepseek-v2-lite", smoke=True), n_layers=4)
    M = make_model(cfg)
    params = M.init(jax.random.PRNGKey(7))
    S, n_prompt = 32, 6
    prompt = (jnp.arange(1, n_prompt + 1, dtype=jnp.int32) * 37 % cfg.vocab_size)[None]
    _, cache = jax.jit(lambda p, t: M.prefill(p, tokens=t, S_max=S))(params, prompt)
    if mode == "verify":
        ctx = _tree_verify_ctx(n_prompt, S)
        tokens = jnp.arange(8, dtype=jnp.int32)[None] * 11 % cfg.vocab_size
    else:
        ctx = Ctx(mode="full", make_cache=S,
                  positions=jnp.arange(n_prompt, dtype=jnp.int32)[None])
        tokens, cache = prompt, None
    h = M._embed(params, tokens)
    with override_flags(scan_layers=scan_layers):
        got_h, got_cache, got_n = jax.jit(lambda p, h, c: apply_model(cfg, p, h, ctx, c))(
            params, h, cache)
    want_h, want_groups, want_n = jax.jit(lambda p, h, c: _per_layer(cfg, p, h, ctx, c))(
        params, h, cache)
    _assert_close(got_h, want_h)
    for got, want in zip(jax.tree.leaves(got_cache["groups"]), jax.tree.leaves(want_groups)):
        _assert_close(got, want)
    assert int(got_n) == int(want_n) > 0


def _assert_close(got, want):
    """Within 1e-6 of ``want``'s largest magnitude: the CPU's grouped matmul
    contracts over every group at once, so more groups sum a row's products
    in another order (a lone MoE layer already differs by 2-3 ulp, and the
    same loop jitted and eager differ by 1.6e-6 at these hidden states)."""
    got, want = np.asarray(got), np.asarray(want)
    err, scale = np.abs(got - want).max(), np.abs(want).max()
    assert err <= 1e-6 * scale, (err, scale)
