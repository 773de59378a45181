"""Sharding rules, arbitrary-TP padding equivalence (paper §4), and
multi-device SPMD correctness (subprocess with forced host devices)."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, resolve_for_tp
from repro.configs.base import ModelConfig
from repro.models.api import make_model
from repro.models.padding import pad_params
from repro.sharding import DEFAULT_RULES, spec_for


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_spec_for_basic_and_fallback():
    mesh = _FakeMesh({"data": 4, "model": 8})
    assert spec_for(mesh, ("embed", "ff"), (64, 128)) == P("data", "model")
    # non-divisible dims fall back to replication per-dim
    assert spec_for(mesh, ("embed", "ff"), (63, 128)) == P(None, "model")
    assert spec_for(mesh, ("heads", "head_dim"), (6, 128)) == P(None, None)


def test_spec_for_no_axis_reuse():
    mesh = _FakeMesh({"data": 4, "model": 8})
    # both dims map to "model": only the first takes it
    sp = spec_for(mesh, ("ff", "vocab"), (128, 256))
    assert sp == P("model", None)


def test_spec_for_multi_axis_batch():
    mesh = _FakeMesh({"pod": 2, "data": 4, "model": 8})
    sp = spec_for(mesh, ("batch", "seq"), (32, 128))
    assert sp == P(("pod", "data"), None)
    # batch=2 divisible only by pod: trailing axes dropped
    sp2 = spec_for(mesh, ("batch", "seq"), (2, 128))
    assert sp2 == P(("pod",), None) or sp2 == P("pod", None)


@pytest.mark.parametrize("arch", ["qwen2.5-14b", "deepseek-coder-33b", "minicpm3-4b"])
def test_tp_padding_equivalence(arch):
    """Zero-padded heads/ff (resolve_for_tp) produce IDENTICAL logits —
    the paper's arbitrary-TP construction."""
    cfg = get_config(arch, smoke=True)
    tp = 3  # deliberately awkward degree
    cfg_p = resolve_for_tp(cfg, tp)
    assert cfg_p.n_heads % tp == 0 and cfg_p.d_ff % tp == 0

    m, mp = make_model(cfg), make_model(cfg_p)
    params = m.init(jax.random.PRNGKey(0))
    params_p = pad_params(cfg, cfg_p, params, mp.init(jax.random.PRNGKey(1)))

    toks = (jnp.arange(20, dtype=jnp.int32).reshape(2, 10) * 11 + 5) % cfg.vocab_size
    a = m.forward_train(params, tokens=toks)
    b = mp.forward_train(params_p, tokens=toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models.api import make_model
from repro.sharding import use_mesh, sharding_for_tree, unbox
from repro.models.transformer import init_model

cfg = get_config("qwen2.5-14b", smoke=True)
m = make_model(cfg)

# single-device reference
params = m.init(jax.random.PRNGKey(0))
toks = (jnp.arange(24, dtype=jnp.int32).reshape(2, 12) * 7 + 1) % cfg.vocab_size
ref = np.asarray(m.forward_train(params, tokens=toks), np.float32)

# SPMD on a (2 data, 4 model) mesh: same math, sharded execution
from repro.launch.mesh import host_device_mesh
mesh = host_device_mesh(model=4, data=2)
sh = sharding_for_tree(mesh, params)
vals = jax.tree.map(jax.device_put, unbox(params), sh)
import jax.tree_util as jtu
from repro.sharding import Param
boxed_leaves, treedef = jtu.tree_flatten(params, is_leaf=lambda x: isinstance(x, Param))
flat_vals = jtu.tree_leaves(vals)
reboxed = jtu.tree_unflatten(treedef, [Param(v, p.axes) for v, p in zip(flat_vals, boxed_leaves)])

with use_mesh(mesh):
    out = jax.jit(lambda p, t: m.forward_train(p, tokens=t))(reboxed, toks)
got = np.asarray(out, np.float32)
np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)

# MoE: tp and ep impls agree under SPMD
from repro.flags import override_flags
cfg2 = get_config("deepseek-moe-16b", smoke=True)
m2 = make_model(cfg2)
p2 = m2.init(jax.random.PRNGKey(0))
ref2 = np.asarray(m2.forward_train(p2, tokens=toks % cfg2.vocab_size), np.float32)
with use_mesh(mesh):
    for impl in ("tp", "ep"):
        with override_flags(moe_impl=impl):
            o = jax.jit(lambda p, t: m2.forward_train(p, tokens=t))(p2, toks % cfg2.vocab_size)
        np.testing.assert_allclose(np.asarray(o, np.float32), ref2, atol=3e-4, rtol=3e-4)

# MoE serving forwards (prefill, then a tree verify with a padding row),
# whose MoE layers read their experts from the whole stack: tp and ep agree
# with one device, expert count included; three MoE layers, scanned
m2 = make_model(dataclasses.replace(cfg2, n_layers=4))
p2 = m2.init(jax.random.PRNGKey(1))
S, V = 24, cfg2.vocab_size
new = jnp.asarray([[3, 5, 7, 0]] * 2, jnp.int32)
rows = jnp.asarray([[12, 13, 14, -1]] * 2, jnp.int32)
pos = jnp.asarray([[12, 13, 13, 12]] * 2, jnp.int32)
cols = jnp.arange(S)
mask = (cols < 12)[None, None, :] | (cols[None, None, :] == rows[:, :, None])
mask = mask.at[:, 1, 12].set(True).at[:, 2, 12].set(True)  # nodes 1, 2 are children of node 0
prefill = jax.jit(lambda p, t: m2.prefill(p, tokens=t, S_max=S))
verify = jax.jit(lambda p, c: m2.spec_forward(p, c, new, pos, rows, mask, count_experts=True))
want_pre, cache = prefill(p2, toks % V)
want_ver, _, want_n = verify(p2, cache)
with use_mesh(mesh):
    for impl in ("tp", "ep"):
        with override_flags(moe_impl=impl):
            got_pre, got_cache = prefill(p2, toks % V)
            got_ver, _, got_n = verify(p2, got_cache)
        np.testing.assert_allclose(np.asarray(got_pre), np.asarray(want_pre), atol=3e-4, rtol=3e-4)
        np.testing.assert_allclose(np.asarray(got_ver[:, :3]), np.asarray(want_ver[:, :3]),
                                   atol=3e-4, rtol=3e-4)
        assert int(got_n) == int(want_n) > 0, (impl, int(got_n), int(want_n))

# collective matmul variants == plain matmul
from repro.core.collective_matmul import matmul_allreduce, matmul_ag_pipelined
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
want = np.asarray(x @ w)
np.testing.assert_allclose(np.asarray(matmul_allreduce(x, w, mesh)), want, atol=1e-4, rtol=1e-4)
np.testing.assert_allclose(np.asarray(matmul_ag_pipelined(x, w, mesh)), want, atol=1e-4, rtol=1e-4)
print("MULTIDEV_OK")
"""


def test_spmd_multidevice_subprocess():
    """8 forced host devices: sharded forward == single-device forward; MoE
    tp/ep agree in training and in serving forwards (prefill, tree verify);
    collective matmuls agree.  Subprocess so the main test
    session keeps one device."""
    r = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT], capture_output=True,
                       text=True, timeout=900, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "MULTIDEV_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
