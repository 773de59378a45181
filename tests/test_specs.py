"""input_specs coverage: every (arch × applicable shape) cell builds its step
function and ShapeDtypeStruct stand-ins without touching devices (the cheap
half of the dry-run; lower+compile runs in launch/dryrun.py)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED, SHAPES, cell_applicable, get_config
from repro.launch.mesh import host_device_mesh
from repro.launch.specs import batch_specs, cache_specs, cell_specs, dryrun_config


@pytest.fixture(scope="module")
def mesh():
    return host_device_mesh()


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_cell_specs_build(arch, shape, mesh):
    ok, why = cell_applicable(get_config(arch), SHAPES[shape])
    if not ok:
        pytest.skip(why)
    step, args, meta = cell_specs(arch, shape, mesh)
    assert callable(step)
    leaves = jax.tree.leaves(args)
    assert leaves, "no inputs?"
    for leaf in leaves:
        assert isinstance(leaf, jax.ShapeDtypeStruct)
        assert leaf.sharding is not None
    assert meta["arch"] == arch


def test_applicability_matrix():
    """long_500k runs exactly for the sub-quadratic archs (DESIGN.md §6)."""
    runs = {a for a in ASSIGNED if cell_applicable(get_config(a), SHAPES["long_500k"])[0]}
    assert runs == {"zamba2-2.7b", "rwkv6-7b"}
    for a in ASSIGNED:  # all other shapes apply everywhere
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert cell_applicable(get_config(a), SHAPES[s])[0]


def test_decode_cache_specs_sharded(mesh):
    cfg = dryrun_config("qwen2.5-14b", mesh)
    cache = cache_specs(cfg, mesh, B=8, S_max=64)
    leaves = jax.tree.leaves(cache)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    # k/v leaves are [U, B, S, hkv, hd]
    shapes = {l.shape for l in leaves if l.ndim == 5}
    assert (48, 8, 64, 8, 128) in shapes


def test_stub_frontend_specs(mesh):
    """Audio arch gets embeds+labels; vlm gets tokens+enc (assignment stubs)."""
    m_cfg = dryrun_config("musicgen-large", mesh)
    b = batch_specs(m_cfg, SHAPES["train_4k"], mesh)
    assert set(b) == {"embeds", "labels"}
    v_cfg = dryrun_config("llama-3.2-vision-90b", mesh)
    b2 = batch_specs(v_cfg, SHAPES["train_4k"], mesh)
    assert set(b2) == {"tokens", "enc"}
    assert b2["enc"].shape == (256, 1024, 8192)
