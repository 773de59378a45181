"""SLO-aware scheduling (repro.serving.scheduler + the queue/stats/runtime
wiring around it).

The contracts under test:

* per-slot adaptive draft depth never changes WHICH tokens a request emits
  — any depth schedule is byte-identical to solo ``generate()`` on every
  serving surface (direct session, continuous, sharded, and with the
  lookahead adopted) — and adds
  ZERO jit traces (depth is a host loop count over the one jitted expand
  program);
* the queue's deadline-aware pop: EDF within a priority class, FIFO
  degeneration without deadlines, and the starvation bound;
* SLO accounting (attainment, slack percentiles) in ``summary()`` /
  ``merge_summary``, plus the serving-accounting bugfixes that rode along:
  rounds-weighted mean acceptance, nan-marked zero-round ratios rendered
  as ``-``, and tracer/round-in-flight hygiene when an absorb fails.
"""

import jax
import numpy as np
import pytest

from repro.core.engine import SpecConfig, SpecEngine, absorb_emitted
from repro.obs.trace import NOOP_SPAN, Tracer
from repro.serving import (
    AdaptiveDepthController,
    ContinuousBatchingRuntime,
    Request,
    RequestQueue,
    SchedulerConfig,
    ShardedServingRuntime,
    VirtualClock,
    merge_summary,
)
from repro.serving.stats import RequestRecord, ServerStats


@pytest.fixture(scope="module")
def sched_engine(dense_pair):
    """An independent draft: the round's lookahead is mostly rolled back."""
    T, D, tp, dp = dense_pair
    cfg = SpecConfig(bs=8, w=4, c=2, d=4, n_cap=64, max_new=24)
    return SpecEngine(T, D, cfg, S_max_t=256, S_max_d=256), tp, dp


@pytest.fixture(scope="module")
def commit_sched_engine(dense_pair):
    """The target as its own draft: the round's lookahead is mostly adopted."""
    T, D, tp, dp = dense_pair
    cfg = SpecConfig(bs=8, w=4, c=2, d=4, n_cap=64, max_new=24)
    return SpecEngine(T, T, cfg, S_max_t=256, S_max_d=256), tp, tp


def _prompt(k, P=8):
    return ((np.arange(1, P + 1) * k + 3) % 128).astype(np.int32)


# ---------------------------------------------------------------------------
# SchedulerConfig / AdaptiveDepthController (pure host logic)
# ---------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError, match="ascending positive"):
        SchedulerConfig(depth_buckets=())
    with pytest.raises(ValueError, match="ascending positive"):
        SchedulerConfig(depth_buckets=(2, 2, 3))
    with pytest.raises(ValueError, match="ascending positive"):
        SchedulerConfig(depth_buckets=(0, 1))
    with pytest.raises(ValueError, match="thresholds"):
        SchedulerConfig(depth_buckets=(1, 2, 4), thresholds=(1.5,))
    with pytest.raises(ValueError, match="ema_alpha"):
        SchedulerConfig(ema_alpha=0.0)


def test_bucket_mapping_default_thresholds():
    # default cuts (1.0, 2.0, 3.0): draft roughly as deep as the measured
    # tokens/round; the boundary belongs to the deeper bucket
    cfg = SchedulerConfig()
    assert [cfg.bucket_for(x) for x in (0.0, 0.9, 1.0, 1.9, 2.5, 3.0, 9.0)] \
        == [1, 1, 2, 2, 3, 4, 4]
    custom = SchedulerConfig(depth_buckets=(2, 4), thresholds=(2.5,))
    assert custom.bucket_for(2.4) == 2 and custom.bucket_for(2.5) == 4


def test_clamp_picks_nearest_bucket_ties_shallow():
    cfg = SchedulerConfig(depth_buckets=(1, 2, 4))
    assert cfg.clamp(0) == 1
    assert cfg.clamp(9) == 4
    assert cfg.clamp(3) == 2  # equidistant from 2 and 4: the cheaper round


def test_controller_ema_round_depth_and_lifecycle():
    ctl = AdaptiveDepthController(SchedulerConfig(ema_alpha=0.5), 3,
                                  default_depth=4)
    # no measurements anywhere: the engine's configured depth
    assert ctl.round_depth([True, True, False]) == 4
    ctl.seed_slot(0)  # no histogram, no explicit seed -> still no prior
    assert ctl.slot_ema(0) is None
    ctl.observe(0, 1)  # first observation adopts the measurement outright
    assert ctl.slot_ema(0) == 1.0
    ctl.observe(0, 0)
    assert ctl.slot_ema(0) == pytest.approx(0.5)
    assert ctl.slot_depth(0) == 1
    ctl.observe(1, 4)  # slot 1 accepts deeply
    assert ctl.slot_depth(1) == 4
    # the round runs at the max over OCCUPIED slots only
    assert ctl.round_depth([True, False, False]) == 1
    assert ctl.round_depth([True, True, False]) == 4
    # retire slot 1: its history must not leak into the next occupant
    ctl.clear_slot(1)
    assert ctl.slot_ema(1) is None
    assert ctl.round_depth([True, True, False]) == 4  # back to default for 1


def test_controller_seeding_priority():
    class _Hist:
        count, mean = 12, 3.2

    explicit = AdaptiveDepthController(
        SchedulerConfig(seed_acceptance=0.5), 1, default_depth=4,
        seed_hist=_Hist())
    explicit.seed_slot(0)
    assert explicit.slot_ema(0) == 0.5  # explicit seed beats the histogram
    warm = AdaptiveDepthController(SchedulerConfig(), 1, default_depth=4,
                                   seed_hist=_Hist())
    warm.seed_slot(0)
    assert warm.slot_ema(0) == pytest.approx(3.2)  # histogram mean
    assert warm.slot_depth(0) == 4


# ---------------------------------------------------------------------------
# deadline-aware queue pop
# ---------------------------------------------------------------------------


def _req(rid, arrival=0.0, deadline=None, priority=0):
    return Request(rid=rid, prompt=_prompt(rid + 1), arrival_s=arrival,
                   deadline_s=deadline, priority=priority)


def test_edf_pop_orders_by_deadline_then_fifo():
    q = RequestQueue()
    q.submit(_req(0, deadline=9.0))
    q.submit(_req(1, deadline=3.0))
    q.submit(_req(2))  # best-effort: after any deadline
    q.submit(_req(3, deadline=3.0))  # ties with rid 1: FIFO
    assert [q.pop_ready(0.0).rid for _ in range(4)] == [1, 3, 0, 2]


def test_priority_classes_dominate_deadlines():
    q = RequestQueue()
    q.submit(_req(0, deadline=1.0, priority=1))  # tightest, but batch class
    q.submit(_req(1, deadline=50.0, priority=0))
    q.submit(_req(2, priority=0))
    assert [q.pop_ready(0.0).rid for _ in range(3)] == [1, 2, 0]


def test_pop_is_exact_fifo_without_deadlines():
    q = RequestQueue()
    for i in range(5):
        q.submit(_req(i))
    assert [q.pop_ready(0.0).rid for _ in range(5)] == [0, 1, 2, 3, 4]


def test_edf_respects_arrival_gating():
    q = RequestQueue()
    q.submit(_req(0, arrival=0.0, deadline=50.0))
    q.submit(_req(1, arrival=5.0, deadline=1e-9 + 5.0))  # tight but future
    assert q.pop_ready(0.0).rid == 0  # rid 1 has not arrived yet
    assert q.pop_ready(0.0) is None


def test_starvation_bound_overrides_edf():
    q = RequestQueue(starvation_s=4.0)
    q.submit(_req(0))  # best-effort, oldest
    q.submit(_req(1, deadline=2.0))
    q.submit(_req(2, deadline=3.0))
    assert q.pop_ready(1.0).rid == 1  # EDF while nobody is starving
    # at t=4 the best-effort head has waited >= starvation_s: it jumps
    assert q.pop_ready(4.0).rid == 0
    assert q.pop_ready(4.0).rid == 2


def test_deadline_before_arrival_rejected():
    with pytest.raises(ValueError, match="deadline_s"):
        Request(rid=0, prompt=_prompt(1), arrival_s=2.0, deadline_s=1.0)


def test_starvation_s_validated():
    with pytest.raises(ValueError, match="starvation_s"):
        RequestQueue(starvation_s=0.0)


# ---------------------------------------------------------------------------
# byte-identity: adaptive depth changes WHEN tokens verify, never WHICH
# ---------------------------------------------------------------------------


def test_depth_schedule_byte_identity_direct_session(sched_engine):
    """Driving the session with a wildly varying per-round depth emits the
    exact solo-generate stream (greedy verification pins it)."""
    eng, tp, dp = sched_engine
    prompt = _prompt(3).reshape(1, -1)
    solo, _ = eng.session(tp, dp).generate(prompt, max_new=16)

    ses = eng.session(tp, dp)
    ses.state = eng._prefill_state(tp, dp, prompt)
    out, done, schedule = [], False, [1, 4, 2, 1, 3, 4, 1, 2]
    for i in range(40):
        if done:
            break
        res = ses.step(depth=schedule[i % len(schedule)])
        _, done = absorb_emitted(out, res.emitted[0], res.n_emitted[0], 16,
                                 eng.cfg.eos_id)
    assert out == solo[0]


def test_depth_variation_adds_no_jit_traces(sched_engine):
    """Depth is a host loop trip count over ONE jitted expand program: after
    warmup, running every bucket adds zero entries to its jit cache."""
    eng, tp, dp = sched_engine
    ses = eng.session(tp, dp)
    ses.state = eng._prefill_state(tp, dp, _prompt(5).reshape(1, -1))
    ses.step(depth=4)  # warm every program at the deepest bucket
    baseline = eng._expand._cache_size()
    for d in (1, 2, 3, 4, 2, 1):
        ses.step(depth=d)
    assert eng._expand._cache_size() == baseline


@pytest.mark.parametrize("surface", ["continuous", "sharded", "commit"])
def test_adaptive_depth_byte_identity_serving(surface, sched_engine,
                                              commit_sched_engine):
    """Adaptive scheduling on: staggered deadlined+best-effort traffic over
    recycled slots still emits solo-identical streams on every surface
    (``commit``: continuous, with a draft whose lookaheads are adopted)."""
    eng, tp, dp = commit_sched_engine if surface == "commit" else sched_engine
    sched = SchedulerConfig(ema_alpha=0.5)
    reqs = [Request(rid=i, prompt=_prompt(i + 1, P=8 + 4 * (i % 2)),
                    arrival_s=0.7 * i, max_new=16,
                    deadline_s=0.7 * i + 40.0 if i % 2 else None)
            for i in range(5)]
    if surface == "sharded":
        rt = ShardedServingRuntime([eng, eng], tp, dp, n_slots=2,
                                   clock=VirtualClock(), scheduler=sched)
    else:
        rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2,
                                       clock=VirtualClock(), scheduler=sched)
    assert rt.submit_trace(reqs) == 5
    results = rt.run()
    assert sorted(results) == [0, 1, 2, 3, 4]
    for r in reqs:
        solo, _ = eng.session(tp, dp).generate(r.prompt.reshape(1, -1), max_new=16)
        assert results[r.rid] == solo[0], \
            f"request {r.rid} diverged from solo generate() on {surface}"
    # the controller actually adapted: the round-depth series exists and
    # every recorded depth is an admissible bucket
    depths = {v for _, s in rt.metrics.series_family("serving_round_depth")
              for _, v in s.samples}
    assert depths and depths <= set(sched.depth_buckets)


def test_adaptive_depth_reduces_round_cost_on_virtual_clock(sched_engine):
    """With the per-expansion cost model, a low-acceptance workload finishes
    the same byte-identical stream in less virtual time under adaptive depth
    than at the fixed global d=4 (shallower rounds are cheaper)."""
    eng, tp, dp = sched_engine

    def _serve(scheduler):
        rt = ContinuousBatchingRuntime(
            eng, tp, dp, n_slots=2,
            clock=VirtualClock(round_dt=1.0, expand_dt=0.25),
            scheduler=scheduler)
        rt.submit_trace(Request(rid=i, prompt=_prompt(i + 2), arrival_s=0.0,
                                max_new=16) for i in range(4))
        res = rt.run()
        return res, rt.clock.now()

    fixed_res, fixed_t = _serve(None)
    # force-shallow schedule stands in for "adaptation found depth 1 pays":
    # identical tokens, strictly cheaper rounds on the expand_dt cost model
    adapt_res, adapt_t = _serve(SchedulerConfig(depth_buckets=(1,)))
    assert adapt_res == fixed_res
    assert adapt_t < fixed_t


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------


def test_summary_slo_fields_and_report(sched_engine):
    """One generous and one impossible deadline: attainment is 1/2, slack
    percentiles are finite, the report tags the late row and appends the
    SLO aggregate."""
    eng, tp, dp = sched_engine
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2, clock=VirtualClock())
    rt.submit(Request(rid=0, prompt=_prompt(1), max_new=8, deadline_s=500.0))
    rt.submit(Request(rid=1, prompt=_prompt(2), max_new=8, deadline_s=1e-6))
    rt.submit(Request(rid=2, prompt=_prompt(3), max_new=8))  # best-effort
    rt.run()
    s = rt.stats.summary()
    assert s["n_deadlined"] == 2
    assert s["slo_attainment"] == pytest.approx(0.5)
    assert np.isfinite(s["slack_p50_s"]) and np.isfinite(s["slack_p10_s"])
    assert rt.stats.records[0].met_deadline is True
    assert rt.stats.records[1].met_deadline is False
    assert rt.stats.records[2].met_deadline is None
    rep = rt.stats.report()
    assert "LATE" in rep and "SLO 50%" in rep


def test_merge_summary_slo_over_fleet():
    def _stats(finishes):
        st = ServerStats()
        for rid, (deadline, finish) in enumerate(finishes):
            st.records[rid] = RequestRecord(
                rid=rid, deadline_s=deadline, finish_s=finish,
                n_rounds=1, n_accepted=1, n_tokens=1)
        return st

    a = _stats([(10.0, 5.0), (10.0, 12.0)])  # met, missed
    b = _stats([(None, 3.0), (4.0, 4.0)])  # best-effort, met exactly
    s = merge_summary([a, b])
    assert s["n_deadlined"] == 3
    assert s["slo_attainment"] == pytest.approx(2 / 3)
    assert s["slack_p50_s"] == pytest.approx(0.0)  # slacks: +5, -2, 0
    # a fleet with no deadlines anywhere nan-marks attainment (no SLO to
    # attain), and the empty fleet keeps mean_acceptance == 0.0 (legacy)
    empty = merge_summary([])
    assert empty["n_deadlined"] == 0 and empty["slo_attainment"] != empty["slo_attainment"]
    assert empty["mean_acceptance"] == 0.0


# ---------------------------------------------------------------------------
# serving-accounting bugfixes
# ---------------------------------------------------------------------------


def test_mean_acceptance_is_rounds_weighted():
    """A 1-round request must not count as much as a 100-round request:
    mean acceptance is total accepted over total rounds, not a mean of
    per-request ratios."""
    st = ServerStats()
    st.records[0] = RequestRecord(rid=0, n_rounds=1, n_accepted=1,
                                  n_tokens=2, finish_s=1.0)
    st.records[1] = RequestRecord(rid=1, n_rounds=100, n_accepted=300,
                                  n_tokens=400, finish_s=1.0)
    got = st.summary()["mean_acceptance"]
    assert got == pytest.approx(301 / 101)
    assert got != pytest.approx((1.0 + 3.0) / 2)  # the old unweighted bias
    assert merge_summary([st])["mean_acceptance"] == pytest.approx(301 / 101)


def test_zero_round_ratios_are_nan_and_render_as_dash():
    r = RequestRecord(rid=0, n_rounds=0, n_accepted=0, finish_s=1.0)
    assert r.acceptance != r.acceptance  # nan, not a fake 0.0
    assert r.compression_ratio != r.compression_ratio
    st = ServerStats()
    st.records[0] = r
    rep = st.report()
    assert "nan" not in rep
    assert " - " in rep or "-" in rep.splitlines()[1]
    # records with rounds are unaffected; a zero-round record contributes
    # weight 0 instead of poisoning the aggregate with nan
    st.records[1] = RequestRecord(rid=1, n_rounds=4, n_accepted=6, finish_s=1.0)
    assert st.summary()["mean_acceptance"] == pytest.approx(6 / 4)


@pytest.mark.parametrize("mode", ["rollback", "commit"])
def test_failing_absorb_leaves_tracer_balanced_and_session_quiescent(
        mode, sched_engine, commit_sched_engine):
    """An absorb that raises (poisoned stream callback) must end the round
    span (tracer balanced) and leave no RoundInFlight orphaned — the fleet
    loop aborts the round on the way out and the session stays usable."""
    eng, tp, dp = commit_sched_engine if mode == "commit" else sched_engine
    tracer = Tracer(clock=lambda: 0.0)

    def bad_stream(rid, toks, done):
        raise RuntimeError("poisoned stream")

    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2,
                                   clock=VirtualClock(), tracer=tracer,
                                   stream=bad_stream)
    rt.submit(Request(rid=0, prompt=_prompt(1), max_new=8))
    rt.submit(Request(rid=1, prompt=_prompt(2), max_new=8))
    with pytest.raises(RuntimeError, match="poisoned stream"):
        rt.run()
    # the round span was closed on the failure path, not leaked open
    assert rt.stepper._round_span is NOOP_SPAN
    rounds = tracer.spans("round")
    assert rounds and all(s.t1 is not None for s in rounds)
    # no orphaned RoundInFlight: the session is quiescent and steppable
    assert rt.stepper.session._inflight is None
    rt.stepper.session._check_quiescent("test")  # does not raise
    res = rt.stepper.step()
    rt.stepper.abort_round(res)  # abort path itself is balanced too
    assert rt.stepper._round_span is NOOP_SPAN
