"""Substrate units: checkpoint atomicity/resume, optimizer math, schedules,
gradient compression, data determinism, fault retry, scheduler policy."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.ckpt import CheckpointManager
from repro.core.scheduler import (AllocationResult, ProfileResult, candidate_depths,
                                  sweep_allocation)
from repro.data import DataConfig, SyntheticLMDataset
from repro.optim import (adamw_init, adamw_update, compress_int8, decompress_int8,
                         warmup_cosine)
from repro.runtime import FaultConfig, retry_step


# -------------------------------------------------------------- checkpoints

def test_ckpt_roundtrip_and_gc():
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=2)
        state = {"a": jnp.arange(5.0), "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
        for s in (3, 7, 9):
            cm.save(s, state, blocking=True)
        assert cm.all_steps() == [7, 9]  # GC keeps 2
        s, restored = cm.restore_latest(state)
        assert s == 9
        np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(5.0))
        assert restored["b"]["c"].dtype == jnp.bfloat16


def test_ckpt_async_then_wait():
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=3)
        state = {"w": jnp.zeros((128, 128))}
        cm.save(1, state, blocking=False)
        cm.wait()
        assert cm.latest_step() == 1


def test_ckpt_ignores_partial_writes():
    """A crash mid-write (temp dir, no MANIFEST) must be invisible."""
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=3)
        cm.save(5, {"x": jnp.ones(3)}, blocking=True)
        os.makedirs(os.path.join(d, "step_000000000009.tmp-dead"), exist_ok=True)
        broken = os.path.join(d, "step_000000000010")
        os.makedirs(broken, exist_ok=True)  # no MANIFEST -> invalid
        assert cm.latest_step() == 5
        s, _ = cm.restore_latest({"x": jnp.ones(3)})
        assert s == 5


def test_ckpt_resume_is_bit_exact():
    """Train 6 steps vs train 3 + restore + 3: identical parameters (the
    fault-tolerance contract, with the deterministic data pipeline)."""
    from repro.configs.base import ModelConfig
    from repro.launch.steps import make_train_step
    from repro.models.api import make_model

    cfg = ModelConfig(name="t", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab_size=64)
    m = make_model(cfg)
    ds = SyntheticLMDataset(DataConfig(cfg.vocab_size, 16, 2, seed=3))
    step = jax.jit(make_train_step(cfg, m))

    def run(params, opt, lo, hi):
        for s in range(lo, hi):
            params, opt, _ = step(params, opt, {"tokens": jnp.asarray(ds.batch(s)["tokens"])})
        return params, opt

    p0 = m.init(jax.random.PRNGKey(0))
    o0 = adamw_init(p0)
    pa, oa = run(p0, o0, 0, 6)

    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        pb, ob = run(p0, o0, 0, 3)
        cm.save(2, (pb, ob), blocking=True)
        s, (pr, orr) = cm.restore_latest((pb, ob))
        pc, oc = run(pr, orr, 3, 6)

    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pc)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------- optimizer

def test_adamw_matches_reference_step():
    params = {"w": jnp.full((4,), 2.0)}
    st_ = adamw_init(params)
    g = {"w": jnp.full((4,), 0.5)}
    lr = 0.1
    new_p, st2 = adamw_update(g, st_, params, lr, b1=0.9, b2=0.95, eps=1e-8,
                              weight_decay=0.0, grad_clip=1e9)
    # step 1: mhat = g, vhat = g^2 -> update = lr * g/|g| = lr
    np.testing.assert_allclose(np.asarray(new_p["w"]), 2.0 - lr, rtol=1e-5)
    assert int(st2.step) == 1


def test_adamw_grad_clip():
    params = {"w": jnp.zeros((3,))}
    st_ = adamw_init(params)
    g = {"w": jnp.full((3,), 100.0)}
    _, st2 = adamw_update(g, st_, params, 0.1, grad_clip=1.0)
    gnorm_clipped = float(jnp.sqrt(jnp.sum(jnp.square(st2.mu["w"])))) / 0.1
    assert gnorm_clipped <= 1.0 + 1e-4


def test_warmup_cosine_shape():
    lr = [float(warmup_cosine(s, peak_lr=1.0, warmup_steps=10, total_steps=100)) for s in range(100)]
    assert lr[0] == 0.0 and abs(lr[10] - 1.0) < 0.11
    assert lr[99] < lr[50] < lr[10]
    assert lr[99] >= 0.1 - 1e-6  # final_frac floor


# -------------------------------------------------------------- compression

@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_int8_compression_error_bound(seed):
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(257,)), jnp.float32)
    q, s = compress_int8(x)
    err = np.max(np.abs(np.asarray(decompress_int8(q, s) - x)))
    assert err <= float(s) / 2 + 1e-7  # half-ulp of the int8 grid


# -------------------------------------------------------------- data

def test_data_deterministic_and_step_indexed():
    ds = SyntheticLMDataset(DataConfig(vocab_size=100, seq_len=16, global_batch=4, seed=1))
    a, b = ds.batch(5)["tokens"], ds.batch(5)["tokens"]
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(ds.batch(5)["tokens"], ds.batch(6)["tokens"])
    assert a.shape == (4, 17) and a.dtype == np.int32


def test_data_is_learnable_markov():
    """The stream must be peaky (predictable) for spec-decoding realism."""
    ds = SyntheticLMDataset(DataConfig(vocab_size=50, seq_len=64, global_batch=8, seed=0))
    toks = ds.batch(0)["tokens"]
    # successor entropy is low: most-frequent successor of each state dominates
    from collections import Counter, defaultdict
    succ = defaultdict(Counter)
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            succ[int(a)][int(b)] += 1
    tops = [c.most_common(1)[0][1] / sum(c.values()) for c in succ.values() if sum(c.values()) >= 5]
    assert np.mean(tops) > 0.5


# -------------------------------------------------------------- fault / sched

def test_retry_step_recovers():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return 42

    assert retry_step(flaky, FaultConfig(backoff_s=0.001)) == 42
    assert len(calls) == 3


def test_retry_step_gives_up():
    def always():
        raise RuntimeError("permanent")

    with pytest.raises(RuntimeError):
        retry_step(always, FaultConfig(max_retries=2, backoff_s=0.001))


def test_candidate_depths_and_allocation():
    assert candidate_depths(ProfileResult(t_draft_s=3e-3, t_target_s=10e-3)) == (3, 4)
    assert candidate_depths(ProfileResult(t_draft_s=10e-3, t_target_s=3e-3)) == (1, 2)
    res = sweep_allocation(8, lambda nt, nd: -abs(nt - 6))
    assert (res.n_target, res.n_draft) == (6, 2)
