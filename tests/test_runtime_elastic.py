"""Elastic re-sharding + serving-mesh helpers (runtime/elastic.py)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import host_device_mesh, make_serving_mesh
from repro.models.api import make_model
from repro.runtime.elastic import reshard_params, submeshes
from repro.sharding import unbox


def test_submeshes_single_device_fallback():
    tgt, drf = submeshes(jax.devices(), n_target=1)
    assert tgt.devices.size >= 1 and drf.devices.size >= 1


def test_make_serving_mesh_fallback():
    """One device: the derived split colocates target and draft on it; an
    explicit split that needs more devices raises instead of stacking both
    roles onto device 0."""
    import pytest

    n = len(jax.devices())
    tgt, drf = make_serving_mesh()  # derived split
    assert "model" in tgt.axis_names and "model" in drf.axis_names
    assert tgt.devices.size + (0 if drf is tgt else drf.devices.size) <= n
    with pytest.raises(ValueError, match="cannot host"):
        make_serving_mesh(6, 2, devices=jax.devices()[:1])
    one = make_serving_mesh(1, 0, devices=jax.devices()[:1])
    assert one[0] is one[1] and one[0].devices.size == 1


def test_make_serving_mesh_replicas():
    """replicas=N returns N disjoint (target, draft) pairs carved from the
    device list (2+2 and 1+1 on four devices); replicas=1 keeps the 2-tuple
    signature; a split that does not fit raises."""
    import pytest

    from repro.launch.mesh import default_split

    devs = jax.devices()[:1] * 4  # four slots: carving is positional
    tgt, drf = make_serving_mesh(2, 2, devices=devs)
    assert tgt.devices.size == 2 and drf.devices.size == 2
    pairs = make_serving_mesh(1, 1, replicas=2, devices=devs)
    assert isinstance(pairs, list) and len(pairs) == 2
    for tgt, drf in pairs:
        assert "model" in tgt.axis_names and "model" in drf.axis_names
        assert tgt.devices.size == 1 and drf.devices.size == 1
    assert default_split(4) == (2, 2) and default_split(4, replicas=2) == (1, 1)
    assert default_split(1) == (1, 0)
    single = make_serving_mesh(1, 0, devices=devs[:1])
    assert isinstance(single, tuple) and len(single) == 2
    with pytest.raises(ValueError):
        make_serving_mesh(1, 0, replicas=0)
    # a partial fit (room for one replica, not two) raises rather than
    # overlapping the second replica onto the first one's devices
    with pytest.raises(ValueError, match="cannot host"):
        make_serving_mesh(1, 0, replicas=2, devices=devs[:1])
    with pytest.raises(ValueError, match="cannot host"):
        make_serving_mesh(2, 2, replicas=2, devices=devs)


def test_reshard_params_preserves_values():
    cfg = get_config("qwen2.5-14b", smoke=True)
    m = make_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    mesh = host_device_mesh()
    vals = reshard_params(params, mesh)
    for a, b in zip(jax.tree.leaves(unbox(params)), jax.tree.leaves(vals)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reshard_then_forward_matches():
    """A re-sharded model (elastic draft/target re-allocation) computes the
    same logits — the invariant that makes reallocation transparent."""
    from repro.sharding import Param, use_mesh
    import jax.tree_util as jtu

    cfg = get_config("qwen2.5-14b", smoke=True)
    m = make_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = (jnp.arange(12, dtype=jnp.int32).reshape(1, 12) * 3 + 1) % cfg.vocab_size
    ref = np.asarray(m.forward_train(params, tokens=toks), np.float32)

    mesh = host_device_mesh()
    vals = reshard_params(params, mesh)
    boxed_leaves, treedef = jtu.tree_flatten(params, is_leaf=lambda x: isinstance(x, Param))
    reboxed = jtu.tree_unflatten(
        treedef, [Param(v, p.axes) for v, p in zip(jtu.tree_leaves(vals), boxed_leaves)]
    )
    with use_mesh(mesh):
        out = np.asarray(m.forward_train(reboxed, tokens=toks), np.float32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
