"""ShardedServingRuntime (repro.serving.router).

The contracts under test: the routing policy (least-loaded replica wins a
popped request, FIFO tie-break so equal load spreads instead of piling onto
replica 0), the shared global queue (admission control spans the fleet),
per-replica/fleet telemetry merging, and — above all — that sharding is
schedule-only: every request's output is byte-identical to a solo
``generate()`` run regardless of which replica served it.
"""

import numpy as np
import pytest

from repro.core.engine import SpecConfig, SpecEngine
from repro.serving import (
    ContinuousBatchingRuntime,
    Request,
    RequestQueue,
    ShardedServingRuntime,
    VirtualClock,
    fleet_report,
    merge_summary,
)


@pytest.fixture(scope="module")
def sharded_engine(dense_pair):
    T, D, tp, dp = dense_pair
    cfg = SpecConfig(bs=8, w=4, c=2, d=2, n_cap=64, max_new=24)
    return SpecEngine(T, D, cfg, S_max_t=256, S_max_d=256), tp, dp


def _prompt(k, P=8):
    return ((np.arange(1, P + 1) * k + 3) % 128).astype(np.int32)


def _fleet(eng, tp, dp, n_rep=2, n_slots=2, **kw):
    # the same engine object N times: states are per-replica, jit cache shared
    return ShardedServingRuntime([eng] * n_rep, tp, dp, n_slots=n_slots,
                                 clock=VirtualClock(), **kw)


# ---------------------------------------------------------------------------
# routing policy (pure, no engine)
# ---------------------------------------------------------------------------


class _Stub:
    def __init__(self, occupied, n_slots, slack=float("inf")):
        self.occupied, self.n_slots = occupied, n_slots
        self.has_free_slot = occupied < n_slots
        self.load = occupied / n_slots
        self._slack = slack

    def deadline_slack(self, now):
        return self._slack


def _router(stubs, last_dispatch=None):
    rt = object.__new__(ShardedServingRuntime)
    rt.steppers = stubs
    rt._last_dispatch = last_dispatch if last_dispatch is not None else [-1] * len(stubs)
    return rt


def test_route_picks_least_loaded():
    rt = _router([_Stub(1, 2), _Stub(0, 2)])
    assert rt._route(0.0) == 1  # 0.5 vs 0.0 load
    rt = _router([_Stub(0, 2), _Stub(1, 2)])
    assert rt._route(0.0) == 0


def test_route_load_is_a_fraction_not_a_count():
    # 3/8 occupied beats 1/2 occupied: the occupancy FRACTION routes (a raw
    # count would send this to replica 0), so heterogeneous slot counts
    # still balance
    rt = _router([_Stub(1, 2), _Stub(3, 8)])
    assert rt._route(0.0) == 1
    rt = _router([_Stub(2, 4), _Stub(3, 4)])
    assert rt._route(0.0) == 0


def test_route_fifo_tiebreak_spreads_equal_load():
    # equal load: the replica whose last admission is OLDEST wins
    rt = _router([_Stub(1, 2), _Stub(1, 2)], last_dispatch=[2, 1])
    assert rt._route(0.0) == 1
    rt = _router([_Stub(1, 2), _Stub(1, 2)], last_dispatch=[1, 2])
    assert rt._route(0.0) == 0


def test_route_skips_full_replicas_and_full_fleet():
    rt = _router([_Stub(2, 2), _Stub(1, 2)])
    assert rt._route(0.0) == 1  # replica 0 is full
    rt = _router([_Stub(2, 2), _Stub(2, 2)])
    assert rt._route(0.0) is None  # fleet full: leave the queue alone


def test_route_slack_breaks_load_ties_before_fifo():
    # equal load, replica 0 has a deadline 2s out, replica 1 has 10s of
    # slack: the new admission steers to the replica with MORE slack even
    # though FIFO (last_dispatch) would have picked replica 0
    rt = _router([_Stub(1, 2, slack=2.0), _Stub(1, 2, slack=10.0)],
                 last_dispatch=[1, 2])
    assert rt._route(0.0) == 1
    # unequal load still dominates: the tighter replica wins when emptier
    rt = _router([_Stub(0, 2, slack=2.0), _Stub(1, 2, slack=10.0)],
                 last_dispatch=[1, 2])
    assert rt._route(0.0) == 0
    # deadline-free fleets (all +inf slack) keep the exact FIFO tie-break
    rt = _router([_Stub(1, 2), _Stub(1, 2)], last_dispatch=[2, 1])
    assert rt._route(0.0) == 1


# ---------------------------------------------------------------------------
# end-to-end sharded serving
# ---------------------------------------------------------------------------


def test_requests_land_on_least_loaded_replica(sharded_engine):
    """Three simultaneous arrivals over 2x2 slots: replica 0 takes the
    first (tie-break), replica 1 the second (now least loaded), replica 0
    the third (equal load, oldest last-admission)."""
    eng, tp, dp = sharded_engine
    rt = _fleet(eng, tp, dp, n_rep=2, n_slots=2)
    rt.submit_trace(Request(rid=i, prompt=_prompt(i + 1), arrival_s=0.0, max_new=8)
                    for i in range(3))
    rt.run()
    assert [rt.replica_of(i) for i in range(3)] == [0, 1, 0]
    # the tags in the per-replica stats agree with the router's view
    for i in range(3):
        rep = rt.replica_of(i)
        assert rt.stats[rep].records[i].replica == rep


def test_sharded_byte_identical_to_solo_generate(sharded_engine):
    """Six staggered requests across 2 replicas: both replicas serve, and
    every output equals its solo generate() run — sharding changes the
    schedule, never the tokens."""
    eng, tp, dp = sharded_engine
    rt = _fleet(eng, tp, dp, n_rep=2, n_slots=2)
    reqs = [Request(rid=i, prompt=_prompt(i + 2, P=8 + 4 * (i % 2)),
                    arrival_s=0.4 * i, max_new=12) for i in range(6)]
    assert rt.submit_trace(reqs) == 6
    results = rt.run()
    assert sorted(results) == list(range(6))
    assert {rt.replica_of(i) for i in range(6)} == {0, 1}
    for r in reqs:
        solo, _ = eng.session(tp, dp).generate(r.prompt.reshape(1, -1), max_new=r.max_new)
        assert results[r.rid] == solo[0], (
            f"request {r.rid} on replica {rt.replica_of(r.rid)} diverged")
    for st in rt.stats:
        assert max(st.occupancy_samples, default=0) <= 2


def test_single_replica_degenerates_to_continuous_runtime(sharded_engine):
    """A 1-replica fleet produces exactly the single-engine runtime's
    outputs for the same trace (one shared stepper implementation)."""
    eng, tp, dp = sharded_engine
    reqs = [dict(rid=i, prompt=_prompt(3 * i + 1), arrival_s=0.5 * i, max_new=8)
            for i in range(3)]
    solo_rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2, clock=VirtualClock())
    solo_rt.submit_trace(Request(**r) for r in reqs)
    fleet = _fleet(eng, tp, dp, n_rep=1, n_slots=2)
    fleet.submit_trace(Request(**r) for r in reqs)
    assert solo_rt.run() == fleet.run()


def test_global_queue_cap_spans_fleet(sharded_engine):
    """One global cap sheds the burst overflow no matter how many replicas
    exist; every admitted request finishes somewhere."""
    eng, tp, dp = sharded_engine
    rt = _fleet(eng, tp, dp, n_rep=2, n_slots=1, queue=RequestQueue(cap=3))
    assert rt.submit_trace(
        Request(rid=i, prompt=_prompt(2 * i + 1), arrival_s=0.0, max_new=8)
        for i in range(5)) == 3
    assert rt.queue.rejected == 2
    results = rt.run()
    assert sorted(results) == [0, 1, 2]
    assert all(len(v) == 8 for v in results.values())


def test_fleet_stats_merge(sharded_engine):
    """merge_summary folds per-replica stats into one global view; the
    fleet report carries per-replica occupancy lines."""
    eng, tp, dp = sharded_engine
    rt = _fleet(eng, tp, dp, n_rep=2, n_slots=2)
    rt.submit_trace(Request(rid=i, prompt=_prompt(i + 4), arrival_s=0.3 * i, max_new=8)
                    for i in range(4))
    rt.run()
    s = rt.summary()
    assert s["n_replicas"] == 2
    assert s["n_finished"] == 4 == sum(s["per_replica_finished"])
    assert s["total_tokens"] == 4 * 8
    assert s["throughput_tok_s"] > 0
    assert len(s["per_replica_occupancy"]) == 2
    assert s["ttft_p50_s"] == s["ttft_p50_s"]  # not NaN
    report = rt.report()
    assert "replica 0:" in report and "replica 1:" in report and "fleet:" in report
    assert fleet_report(rt.stats) == report
    # summary() additionally folds the per-replica accept-depth histograms
    # (union-merged edges); modulo those keys it IS merge_summary.  SLO
    # fields are nan here (no request carried a deadline), so compare
    # nan-aware: nan == nan for this purpose
    base = merge_summary(rt.stats)
    for k, v in base.items():
        got = s[k]
        if isinstance(v, float) and v != v:
            assert got != got, k
        else:
            assert got == v, k
    assert s["accept_depth_hist"]["count"] > 0
    assert s["accept_depth_mean"] == pytest.approx(
        s["accept_depth_hist"]["sum"] / s["accept_depth_hist"]["count"])


def test_long_prefill_on_one_replica_does_not_block_admission_order(sharded_engine):
    """While replica 0 is mid-flight on a long request, a new arrival is
    admitted to replica 1 in the same loop turn (per-replica admission: no
    fleet-wide barrier on one replica's prefill)."""
    eng, tp, dp = sharded_engine
    rt = _fleet(eng, tp, dp, n_rep=2, n_slots=1)
    rt.submit(Request(rid=0, prompt=_prompt(5, P=16), arrival_s=0.0, max_new=20))
    rt.submit(Request(rid=1, prompt=_prompt(6), arrival_s=1.0, max_new=4))
    rt.run()
    assert rt.replica_of(0) == 0 and rt.replica_of(1) == 1
    r0, r1 = rt.stats[0].records[0], rt.stats[1].records[1]
    # rid 1 was admitted while rid 0 was still decoding, not after it retired
    assert r1.admitted_s < r0.finish_s
