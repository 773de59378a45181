"""The always-on round counters (docs/observability.md): dispatches per
jitted program and host seconds blocked in the round's sync
(``SpecStats``), host seconds per round (``ServerStats``).  They count the
same intervals as the round's spans, and they count them whether or not a
tracer is on."""

import numpy as np
import pytest

from repro.core.engine import SpecConfig, SpecEngine
from repro.obs import NULL_TRACER, Tracer, phase_breakdown
from repro.serving import ContinuousBatchingRuntime, Request, VirtualClock

CFG = dict(bs=8, w=4, c=2, d=2, n_cap=64, mode="parallel", max_new=24)


def _prompt(k, P=8):
    return ((np.arange(1, P + 1) * k + 3) % 128).astype(np.int32)


# engine variants: the two round paths, one whose re-root grows two levels
# (grow_per_round 2) and the serial mode, whose re-root grows all d levels
VARIANTS = {"lockstep": {}, "async": dict(async_rounds=True),
            "async-bs16": dict(async_rounds=True, bs=16), "serial": dict(mode="serial")}


@pytest.fixture(scope="module")
def engines(dense_pair):
    """The engine variants over an independent draft, so the async lookahead
    is rolled back."""
    T, D, tp, dp = dense_pair
    return {name: SpecEngine(T, D, SpecConfig(**{**CFG, **kw}), S_max_t=256, S_max_d=256)
            for name, kw in VARIANTS.items()}, tp, dp


def _serve(engines, mode, tracer=None):
    eng, tp, dp = engines[0][mode], engines[1], engines[2]
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2, clock=VirtualClock(),
                                   tracer=tracer)
    rt.submit_trace([Request(rid=i, prompt=_prompt(i + 1, P=8 + 4 * (i % 2)),
                             arrival_s=0.7 * i, max_new=12) for i in range(4)])
    results = rt.run()
    assert sorted(results) == [0, 1, 2, 3]
    return rt


@pytest.mark.parametrize("mode", list(VARIANTS))
def test_every_round_dispatches_one_verify(engines, mode):
    rt = _serve(engines, mode)
    spec, n = rt.stepper.spec_stats, rt.stats.rounds
    assert n > 0 and spec.rounds == n
    assert spec.dispatches["jit_verify"] == spec.dispatches["jit_compact"] == n
    # four admissions, four retirements
    assert spec.dispatches["jit_target_prefill"] == spec.dispatches["jit_draft_prefill"] == 4
    assert spec.dispatches["jit_install_slot"] == spec.dispatches["jit_zero_slot"] == 8
    eng = engines[0][mode]
    if eng.cfg.async_rounds:
        # a speculative re-root every round, a second on each rolled-back one
        rollbacks = spec.spec_rounds - spec.spec_commits
        assert rollbacks > 0
        assert spec.dispatches["jit_predict_accept"] == n
        reroots = n + rollbacks
    else:
        reroots = n
    # each re-root fills the prefix and grows the first level in one program
    assert (spec.dispatches["jit_reroot"] == spec.dispatches["jit__unknown"]
            == spec.dispatches["jit_fill_prefix"] == reroots)
    # d per round in parallel mode, the rest of each re-root's growth (all d
    # levels in serial mode), and each admission's
    grow, d = eng.grow_per_round, eng.cfg.d
    parallel = eng.cfg.mode == "parallel"
    n_grow = grow if parallel else d
    assert grow == (2 if eng.cfg.bs == 16 else 1)
    assert spec.dispatches["jit_expand"] == (d * n if parallel else 0) + (n_grow - 1) * reroots + grow * 4


@pytest.mark.parametrize("mode", ["lockstep", "async"])
def test_round_time_is_the_round_spans(engines, mode):
    """``round_s`` covers the ``round`` spans' interval and ``sync_s`` the
    ``sync_emitted`` spans', so each agrees with the recording tracer's
    spans over the same run."""
    tracer = Tracer()
    rt = _serve(engines, mode, tracer)
    spec, server = rt.stepper.spec_stats, rt.stats
    bd = phase_breakdown(tracer)
    assert bd["n_rounds"] == server.rounds
    assert server.round_s == pytest.approx(bd["round_total_s"], rel=0.05)
    assert spec.sync_s == pytest.approx(bd["phase_s"]["sync_emitted"], rel=0.05)
    assert 0 < spec.sync_s <= server.round_s
    # the longest round's host time outside its sync: within the longest
    # round span (the stamps sit a few microseconds inside the span's)
    longest = max(s.dur for s in tracer.spans("round"))
    assert 0 < server.round_max_s <= longest + 1e-3


@pytest.mark.parametrize("mode", ["lockstep", "async"])
def test_counters_do_not_depend_on_the_tracer(engines, mode):
    """The same run counts the same dispatches and rounds untraced, under a
    recording tracer and under the benchmark's profiler tracer."""
    from bench.obs import ProfilerTracer

    runs = {name: _serve(engines, mode, tracer)
            for name, tracer in (("null", NULL_TRACER), ("recording", Tracer()),
                                 ("profiler", ProfilerTracer()))}
    assert len(NULL_TRACER.spans()) == 0
    null = runs["null"]
    for rt in runs.values():
        spec, server = rt.stepper.spec_stats, rt.stats
        assert spec.dispatches == null.stepper.spec_stats.dispatches
        assert (spec.rounds, spec.spec_commits, server.rounds) == (
            null.stepper.spec_stats.rounds, null.stepper.spec_stats.spec_commits,
            null.stats.rounds)
        assert 0 < spec.sync_s <= server.round_s
        assert 0 < server.round_max_s <= server.round_s


def test_generate_counts_into_its_stats(engines):
    eng, tp, dp = engines[0]["async"], engines[1], engines[2]
    session = eng.session(tp, dp)
    out, stats = session.generate(_prompt(3).reshape(1, -1), max_new=12)
    assert session.stats is stats and len(out[0]) == 12
    assert stats.dispatches["jit_verify"] == stats.rounds > 0
    assert stats.dispatches["jit_target_prefill"] == stats.dispatches["jit_seed"] == 1
    assert 0 < stats.sync_s <= stats.wall_s


def test_chain_session_times_its_sync():
    import jax

    from repro.configs import get_config
    from repro.core.chain_engine import ChainConfig, ChainSpecEngine
    from repro.models.api import make_model

    cfg = get_config("rwkv6-7b", smoke=True)
    m = make_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    eng = ChainSpecEngine(m, m, ChainConfig(k=4, max_new=12), 256, 256)
    tracer = Tracer()
    prompt = (np.arange(1, 9, dtype=np.int32) % cfg.vocab_size).reshape(1, 8)
    _, stats = eng.session(p, p, tracer=tracer).generate(prompt)
    syncs = tracer.spans("sync_emitted")
    assert len(syncs) == stats.rounds > 0
    assert 0 < stats.sync_s <= sum(s.dur for s in syncs) <= stats.wall_s
