"""The central system test (paper correctness contract): with greedy
verification, speculative output equals target-only greedy decoding exactly —
whether the round's lookahead is adopted or rolled back, at any draft depth,
with any draft."""

import jax
import numpy as np
import pytest

from conftest import greedy_reference
from repro.configs import get_config
from repro.core.engine import SpecConfig, SpecEngine
from repro.models.api import make_model


def _run(T, D, tp, dp, prompt, max_new=24, **kw):
    cfg = SpecConfig(**{**dict(bs=8, w=4, c=2, d=2, n_cap=64, max_new=max_new), **kw})
    eng = SpecEngine(T, D, cfg, S_max_t=256, S_max_d=256)
    return eng.session(tp, dp).generate(prompt)


# d=2 at bs=8, and d=3 at bs=16, whose re-root grows two levels
# (grow_per_round 2) before the round's d expansions
DEPTHS = {"d2": dict(d=2), "d3-bs16": dict(d=3, bs=16)}


@pytest.mark.parametrize("depth", list(DEPTHS))
def test_greedy_equality_independent_draft(dense_pair, depth):
    T, D, tp, dp = dense_pair
    prompt = (np.arange(1, 9, dtype=np.int32) % 128).reshape(1, 8)
    ref = greedy_reference(T, tp, prompt, 24)
    out, stats = _run(T, D, tp, dp, prompt, **DEPTHS[depth])
    assert out[0] == ref[0]
    assert stats.rounds > 0 and stats.emitted >= 24


@pytest.mark.parametrize("depth", list(DEPTHS))
def test_greedy_equality_self_draft(dense_pair, depth):
    """draft == target: high acceptance, deep chains — stresses re-rooting."""
    T, _, tp, _ = dense_pair
    prompt = (np.arange(3, 11, dtype=np.int32) % 128).reshape(1, 8)
    ref = greedy_reference(T, tp, prompt, 32)
    out, stats = _run(T, T, tp, tp, prompt, max_new=32, **DEPTHS[depth])
    assert out[0] == ref[0]
    assert stats.compression_ratio > 1.2  # peaked logits -> real acceptance


@pytest.mark.parametrize("draft", ["independent", "self"])
def test_greedy_equality_batched(dense_pair, draft):
    T, D, tp, dp = dense_pair
    D, dp = (T, tp) if draft == "self" else (D, dp)
    prompt = (np.arange(16, dtype=np.int32).reshape(2, 8) * 3 + 1) % 128
    ref = greedy_reference(T, tp, prompt, 16)
    out, _ = _run(T, D, tp, dp, prompt, max_new=16)
    assert out == ref


@pytest.mark.parametrize("arch", ["mixtral-8x22b", "minicpm3-4b", "deepseek-moe-16b"])
def test_greedy_equality_arch_families(arch):
    """Tree spec holds across MoE and MLA attention variants (smoke configs)."""
    cfg = get_config(arch, smoke=True)
    T = make_model(cfg)
    tp = T.init(jax.random.PRNGKey(0))
    tp["lm_head"].value = tp["lm_head"].value * 4.0
    prompt = (np.arange(1, 7, dtype=np.int32) % cfg.vocab_size).reshape(1, 6)
    ref = greedy_reference(T, tp, prompt, 12)
    out, _ = _run(T, T, tp, tp, prompt, max_new=12)
    assert out[0] == ref[0]
