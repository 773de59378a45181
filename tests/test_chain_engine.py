"""Chain-mode speculation for recurrent-state archs (DESIGN.md §6):
greedy-equality + committed-state consistency on rwkv6 / zamba2."""

import jax
import numpy as np
import pytest

from conftest import greedy_reference
from repro.configs import get_config
from repro.core.chain_engine import ChainConfig, ChainSpecEngine
from repro.models.api import make_model


def _mk(arch, seed=0, peak=4.0):
    cfg = get_config(arch, smoke=True)
    m = make_model(cfg)
    p = m.init(jax.random.PRNGKey(seed))
    p["lm_head"].value = p["lm_head"].value * peak
    return cfg, m, p


@pytest.mark.parametrize("arch", ["rwkv6-7b", "zamba2-2.7b"])
@pytest.mark.parametrize("mode", ["serial", "parallel"])
def test_chain_greedy_equality_self_draft(arch, mode):
    cfg, T, tp = _mk(arch)
    prompt = (np.arange(1, 9, dtype=np.int32) % cfg.vocab_size).reshape(1, 8)
    ref = greedy_reference(T, tp, prompt, 24)
    eng = ChainSpecEngine(T, T, ChainConfig(k=4, mode=mode, max_new=24), 256, 256)
    out, stats = eng.session(tp, tp).generate(prompt)
    assert out[0] == ref[0]
    # self-draft on peaked logits accepts aggressively
    assert stats.compression_ratio > 1.5
    if mode == "parallel":
        assert stats.reused_chains > 0  # full-acceptance chains get reused


@pytest.mark.parametrize("arch", ["rwkv6-7b", "zamba2-2.7b"])
def test_chain_greedy_equality_independent_draft(arch):
    """Partial acceptance exercises the recompute-from-pre-state rollback."""
    cfg, T, tp = _mk(arch, seed=0)
    _, _, dp = _mk(arch, seed=7)
    prompt = (np.arange(2, 10, dtype=np.int32) % cfg.vocab_size).reshape(1, 8)
    ref = greedy_reference(T, tp, prompt, 20)
    for mode in ("serial", "parallel"):
        eng = ChainSpecEngine(T, T, ChainConfig(k=4, mode=mode, max_new=20), 256, 256)
        out, _ = eng.session(tp, dp).generate(prompt)
        assert out[0] == ref[0], mode


def test_chain_state_commit_is_prefix_exact():
    """chain_forward(u, n) must leave the cache exactly as if only u[:n] had
    been decoded step-by-step (the §3.2 consistency analogue for state)."""
    cfg, T, tp = _mk("rwkv6-7b")
    prompt = (np.arange(1, 9, dtype=np.int32) % cfg.vocab_size).reshape(1, 8)
    import jax.numpy as jnp

    _, cache0 = jax.jit(lambda p, t: T.prefill(p, tokens=t, S_max=64))(tp, jnp.asarray(prompt))
    u = jnp.asarray([[5, 9, 13, 21]], jnp.int32)
    n = 2
    _, cache_chain = T.chain_forward(tp, cache0, u, n, 64)

    cache_ref = cache0
    for i in range(n):
        _, cache_ref = T.decode_step(tp, cache_ref, u[:, i : i + 1], 64)

    ref_leaves = jax.tree.leaves(cache_ref)
    got_leaves = jax.tree.leaves(cache_chain)
    for a, b in zip(ref_leaves, got_leaves):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-5)
