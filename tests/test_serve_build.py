"""The serving entry point's builders: config pair, engine placement, compile
cache (launch/serve.py)."""

import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import greedy_reference
from repro.launch import serve


def test_serving_configs_cut_depth_and_dtype():
    t, d = serve.serving_configs("deepseek-coder-33b", "deepseek-coder-1.3b", smoke=False,
                                 target_layers=6, dtype="bfloat16")
    assert (t.n_layers, t.d_model, t.n_heads, t.n_kv_heads, t.d_ff) == (6, 7168, 56, 8, 19200)
    assert (d.n_layers, d.d_model, d.d_ff) == (24, 2048, 5504)  # the draft stays whole
    assert t.dtype == t.param_dtype == d.dtype == d.param_dtype == "bfloat16"
    assert t.vocab_size == d.vocab_size == 32256
    ts, _ = serve.serving_configs("deepseek-coder-33b", "deepseek-coder-1.3b")
    assert ts.d_model == 64 and ts.dtype == "float32"  # CPU default: smoke shapes


def test_build_engine_places_params_and_matches_greedy():
    """One device: target and draft colocate on it, the parameters live
    there, and generate() equals target-only greedy decoding."""
    cfgT, cfgD = serve.serving_configs("deepseek-coder-33b", "deepseek-coder-1.3b")
    eng, tp, dp, _ = serve.build_engine(cfgT, cfgD, max_new=10, S_max=128)
    dev = jax.devices()[0]
    assert eng.mesh_target is eng.mesh_draft
    for leaf in jax.tree.leaves((tp, dp)):
        assert leaf.sharding.device_set == {dev}
    prompt = np.arange(16, dtype=np.int32).reshape(2, 8) * 7 % cfgT.vocab_size
    out, _ = eng.session(tp, dp).generate(prompt)
    assert out == greedy_reference(eng.target, tp, prompt, 10, S_max=128)


def test_build_engine_refuses_a_split_that_does_not_fit():
    cfgT, cfgD = serve.serving_configs("deepseek-coder-33b", "deepseek-coder-1.3b")
    with pytest.raises(ValueError, match="cannot host"):
        serve.build_engine(cfgT, cfgD, n_target=len(jax.devices()), n_draft=1)


@pytest.mark.parametrize("kw", [dict(mode="serial"), dict(async_rounds=False)],
                         ids=["mode-serial", "async-false"])
def test_build_engine_accepts_only_the_pipelined_round(monkeypatch, kw):
    """The benchmark harness still names the round schedule; any schedule
    but the pipelined one is refused before a weight is drawn."""
    cfgT, cfgD = serve.serving_configs("deepseek-coder-33b", "deepseek-coder-1.3b")

    def drawn(*a, **k):
        raise AssertionError("weights drawn before the schedule was checked")

    monkeypatch.setattr(serve, "init_params", drawn)
    monkeypatch.setattr(serve, "make_model", drawn)
    with pytest.raises(ValueError, match="round schedule"):
        serve.build_engine(cfgT, cfgD, **kw)


def test_enable_compile_cache_env_or_checkout(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper leaves JAX to use it;
    without, the cache goes to the fixed directory inside the checkout."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        serve.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        serve.enable_compile_cache()
        root = serve.Path(serve.__file__).resolve().parents[3]
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
        assert (root / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


_SHARDED_KERNELS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding
from repro.core import kv as kvm
from repro.flags import override_flags
from repro.models.attention import KV_AXES, Q_AXES
from repro.sharding import SERVING_RULES, shard_local, spec_for, use_mesh
from repro.kernels import ops

rng = np.random.default_rng(0)
mesh = Mesh(np.array(jax.devices()), ("model",))
f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
q, k, v = f32(1, 8, 4, 128), f32(1, 256, 2, 128), f32(1, 256, 2, 128)
mask = jnp.asarray(rng.random((1, 8, 256)) < 0.5)
q6, k3 = f32(1, 8, 6, 128), f32(1, 256, 3, 128)
x, wg, wu = f32(8, 128), f32(128, 256), f32(128, 256)
cache = {"len": jnp.zeros((), jnp.int32),
         "groups": [({"k": f32(2, 2, 64, 2, 128), "v": f32(2, 2, 64, 2, 128)},)]}
src = jnp.asarray([[3, 4, -1], [5, 1, 2]], jnp.int32)
dst = jnp.asarray([[10, 11, 12], [20, 21, 22]], jnp.int32)
mv = jnp.ones((2, 3), bool)
donor = jax.tree.map(lambda a: a[:, :1] + 1.0 if a.ndim else a, cache)

def run():
    att = shard_local(ops.tree_attention, (q, k, v, mask),
                      (Q_AXES, KV_AXES, KV_AXES, ("batch", None, "kv_seq")), Q_AXES,
                      local_dims=((0, 2), (0, 2), (0, 2), (0,)))
    sw = shard_local(ops.fused_swiglu, (x, wg, wu), ((None, None), ("embed", "ff"), ("embed", "ff")),
                     (None, "ff"), local_dims=((), (1,), (1,)))
    with override_flags(use_pallas_kv_moves=True):
        moved = kvm.apply_moves(cache, src, dst, mv)
        inst = kvm.install_slot(cache, donor, 1)
    # 6 query / 3 kv heads: query heads could split in two, kv heads cannot,
    # so the kernel must see whole operands
    odd = shard_local(ops.tree_attention, (q6, k3, k3, mask),
                      (Q_AXES, KV_AXES, KV_AXES, ("batch", None, "kv_seq")), Q_AXES,
                      local_dims=((0, 2), (0, 2), (0, 2), (0,)))
    return att, sw, moved, inst, odd

want = run()
place = lambda t, axes: jax.device_put(t, NamedSharding(mesh, spec_for(mesh, axes, t.shape, SERVING_RULES)))
with use_mesh(mesh, SERVING_RULES):
    k, v = place(k, KV_AXES), place(v, KV_AXES)
    cache = jax.device_put(cache, kvm.cache_shardings(mesh, cache, SERVING_RULES))
    assert cache["groups"][0][0]["k"].sharding.spec[3] == "model"
    got = jax.jit(run)()
for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)
print("SHARDED_KERNELS_OK")
"""


def test_kernels_per_shard_match_one_device_subprocess():
    """On a 2-device serving mesh the Pallas kernels run per kv-head / ff
    shard (shard_local) and give what they give on one device.  Subprocess so
    the main test session keeps one device."""
    r = subprocess.run([sys.executable, "-c", _SHARDED_KERNELS], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert "SHARDED_KERNELS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
