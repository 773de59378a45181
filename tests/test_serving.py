"""Continuous-batching serving runtime (repro.serving).

The correctness contract: a request's emitted stream is byte-identical to a
solo ``generate()`` run no matter when it was admitted, which slot it landed
in, or what its neighbors were doing — plus slot-recycling hygiene (a retired
slot's KV/tree state cannot leak into its successor) and queue/admission
invariants under a burst trace.
"""

import jax
import numpy as np
import pytest

from repro.core import kv as kvm
from repro.core.engine import SpecConfig, SpecEngine
from repro.serving import ContinuousBatchingRuntime, Request, RequestQueue, VirtualClock


@pytest.fixture(scope="module")
def serving_engine(dense_pair):
    T, D, tp, dp = dense_pair
    cfg = SpecConfig(bs=8, w=4, c=2, d=2, n_cap=64, max_new=24)
    return SpecEngine(T, D, cfg, S_max_t=256, S_max_d=256), tp, dp


def _prompt(k, P=8):
    return ((np.arange(1, P + 1) * k + 3) % 128).astype(np.int32)


# ---------------------------------------------------------------------------
# greedy equivalence under continuous batching
# ---------------------------------------------------------------------------


def test_continuous_matches_solo_generate(serving_engine):
    """Five staggered requests through two slots: every output equals its
    solo generate() run, and lifetimes overlap (mid-flight admission)."""
    eng, tp, dp = serving_engine
    reqs = [Request(rid=i, prompt=_prompt(i + 1, P=8 + 4 * (i % 2)),
                    arrival_s=0.7 * i, max_new=16) for i in range(5)]
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2, clock=VirtualClock())
    assert rt.submit_trace(reqs) == 5
    results = rt.run()

    assert sorted(results) == [0, 1, 2, 3, 4]
    for r in reqs:
        solo, _ = eng.session(tp, dp).generate(r.prompt.reshape(1, -1), max_new=r.max_new)
        assert results[r.rid] == solo[0], f"request {r.rid} diverged from solo generate()"

    # continuous batching actually happened: some request was admitted while
    # another was still in flight (overlapping [admit, finish) round ranges)
    recs = sorted(rt.stats.records.values(), key=lambda r: r.admit_round)
    overlaps = [
        (a.rid, b.rid)
        for a in recs for b in recs
        if a.rid != b.rid and a.admit_round < b.finish_round and b.admit_round < a.finish_round
    ]
    assert overlaps, "no overlapping request lifetimes — not continuous batching"
    assert max(rt.stats.occupancy_samples) == 2  # both slots were in use at once


def test_streaming_delivery(serving_engine):
    """The stream callback sees every token, in order, before run() returns."""
    eng, tp, dp = serving_engine
    got = {}
    rt = ContinuousBatchingRuntime(
        eng, tp, dp, n_slots=2, clock=VirtualClock(),
        stream=lambda rid, toks, done: got.setdefault(rid, []).extend(toks),
    )
    reqs = [Request(rid=i, prompt=_prompt(7 + i), arrival_s=0.0, max_new=12) for i in range(3)]
    rt.submit_trace(reqs)
    results = rt.run()
    assert got == results


def test_live_submit_after_trace_run(serving_engine):
    """The runtime stays usable after a trace: a later submit with the
    default arrival_s=0.0 arrives 'now' instead of violating queue order."""
    eng, tp, dp = serving_engine
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=VirtualClock())
    rt.submit(Request(rid=0, prompt=_prompt(4), arrival_s=2.0, max_new=8))
    rt.run()
    assert rt.submit(Request(rid=1, prompt=_prompt(6), max_new=8))  # arrival in the past
    results = rt.run()
    assert sorted(results) == [0, 1]
    solo, _ = eng.session(tp, dp).generate(_prompt(6).reshape(1, -1), max_new=8)
    assert results[1] == solo[0]
    assert rt.stats.summary()["n_finished"] == 2


def test_live_submit_mid_run_does_not_break_trace_feed(serving_engine):
    """A stream-callback submit() racing a not-yet-fed trace entry: the live
    request is clamped to 'now', and the trace entry (older true arrival)
    still feeds cleanly on the next loop turn — no ordering crash, all three
    requests served."""
    eng, tp, dp = serving_engine
    sent = []

    def stream(rid, toks, done):
        if not sent and rt.clock.now() >= 3.0:
            sent.append(rid)
            assert rt.submit(Request(rid=2, prompt=_prompt(8), max_new=4))

    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=VirtualClock(),
                                   stream=stream)
    rt.submit(Request(rid=0, prompt=_prompt(4), arrival_s=0.0, max_new=12))
    rt.submit(Request(rid=1, prompt=_prompt(5), arrival_s=2.5, max_new=4))
    results = rt.run()
    assert sorted(results) == [0, 1, 2]
    solo, _ = eng.session(tp, dp).generate(_prompt(8).reshape(1, -1), max_new=4)
    assert results[2] == solo[0]


def test_eos_inherited_from_engine(dense_pair, serving_engine):
    """A Request without an explicit eos_id follows the ENGINE's eos_id, so
    the byte-identical contract holds for engines that stop early."""
    T, D, tp, dp = dense_pair
    base, _, _ = serving_engine
    prompt = _prompt(9)
    probe, _ = base.session(tp, dp).generate(prompt.reshape(1, -1), max_new=20)
    eos = probe[0][10]  # a token the greedy stream provably reaches
    eng = SpecEngine(T, D, SpecConfig(bs=8, w=4, c=2, d=2, n_cap=64, max_new=20,
                                      eos_id=eos), S_max_t=256, S_max_d=256)
    solo, _ = eng.session(tp, dp).generate(prompt.reshape(1, -1), max_new=20)
    assert eos in solo[0]
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=VirtualClock())
    rt.submit(Request(rid=0, prompt=prompt, max_new=20))
    assert rt.run()[0] == solo[0]


def test_immediate_eos_request_record_shape(serving_engine):
    """A request whose very first verified token is its EOS: it finishes in
    its first round with exactly that one token, and the telemetry record is
    fully formed (TTFT present, finish stamped, one-round lifetime)."""
    eng, tp, dp = serving_engine
    prompt = _prompt(13)
    probe, _ = eng.session(tp, dp).generate(prompt.reshape(1, -1), max_new=4)
    eos = probe[0][0]  # the first greedy token
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2, clock=VirtualClock())
    rt.submit(Request(rid=0, prompt=prompt, max_new=16, eos_id=eos))
    results = rt.run()
    assert results[0] == [eos]
    rec = rt.stats.records[0]
    assert rec.n_tokens == 1 and rec.n_rounds == 1
    assert rec.ttft_s is not None and rec.finish_s is not None
    assert rec.finish_round == rec.admit_round + 1
    assert rec.first_token_s == rec.finish_s
    assert rec.tok_per_s is not None  # finish strictly after admit (one round)
    s = rt.stats.summary()
    assert s["n_finished"] == 1 and s["total_tokens"] == 1
    assert s["ttft_p50_s"] == pytest.approx(rec.ttft_s)


def test_plen_budget_single_definition(serving_engine):
    """The KV-budget bound has ONE definition: the serving runtime inherits
    engine.plen_budget verbatim (drift here silently breaks the
    byte-identical contract for requests near the budget)."""
    eng, tp, dp = serving_engine
    assert eng.plen_budget == min(eng.S_max_t, eng.S_max_d) - 2 * eng.cfg.bs
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=VirtualClock())
    assert rt._plen_limit == eng.plen_budget
    assert rt.stepper.plen_limit == eng.plen_budget


# ---------------------------------------------------------------------------
# slot recycling
# ---------------------------------------------------------------------------


def test_slot_recycling_no_leakage(serving_engine):
    """Two requests serially through ONE slot: the successor's output is
    unaffected by its predecessor, and release physically zeroes the rows."""
    eng, tp, dp = serving_engine
    a, b = _prompt(5, P=12), _prompt(11, P=8)
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=VirtualClock())
    rt.submit(Request(rid=0, prompt=a, arrival_s=0.0, max_new=16))
    rt.submit(Request(rid=1, prompt=b, arrival_s=0.0, max_new=16))
    results = rt.run()

    solo_b, _ = eng.session(tp, dp).generate(b.reshape(1, -1), max_new=16)
    assert results[1] == solo_b[0], "retired slot state leaked into its successor"

    # after the final release, every cache row of the slot is physically zero
    for cache in (rt.state.tcache, rt.state.dcache):
        leaves = jax.tree.leaves(cache["groups"])
        assert leaves and all(not np.asarray(leaf).any() for leaf in leaves)


def test_release_slot_targets_one_row(serving_engine):
    """zero_slot/reset_slot touch exactly the released row."""
    eng, tp, dp = serving_engine
    sess = eng.session(tp, dp, n_slots=2)
    sess.admit_slot(0, _prompt(3))
    sess.admit_slot(1, _prompt(4))
    before = [np.asarray(x) for x in jax.tree.leaves(sess.state.tcache["groups"])]
    sess.release_slot(0)
    after = [np.asarray(x) for x in jax.tree.leaves(sess.state.tcache["groups"])]
    for b4, af in zip(before, after):
        assert not af[:, 0].any(), "released row not cleared"
        np.testing.assert_array_equal(af[:, 1], b4[:, 1])  # neighbor untouched
    assert not np.asarray(sess.state.tr.valid[0]).any()
    assert np.asarray(sess.state.tr.valid[1]).any()


def test_install_zero_slot_roundtrip():
    """kv.install_slot / kv.zero_slot unit behaviour on a toy cache."""
    import jax.numpy as jnp

    def mk(v):
        return {"len": jnp.zeros((), jnp.int32),
                "groups": [{"k": v, "v": 2 * v}]}

    big = mk(jnp.zeros((2, 3, 4, 5), jnp.float32))
    one = mk(jnp.asarray(np.random.default_rng(0).normal(size=(2, 1, 4, 5)), jnp.float32))
    out = kvm.install_slot(big, one, 1)
    np.testing.assert_allclose(np.asarray(out["groups"][0]["k"][:, 1]), one["groups"][0]["k"][:, 0])
    assert not np.asarray(out["groups"][0]["k"][:, 0]).any()
    out2 = kvm.zero_slot(out, 1)
    assert not np.asarray(out2["groups"][0]["k"]).any()
    np.testing.assert_allclose(np.asarray(out2["groups"][0]["v"][:, 2]),
                               np.asarray(out["groups"][0]["v"][:, 2]))


# ---------------------------------------------------------------------------
# queue / admission invariants
# ---------------------------------------------------------------------------


def test_queue_depth_o1_bookkeeping():
    """depth() is an O(1) arrived-count for monotonic ``now`` (the runtimes'
    usage), stays exact as requests arrive/pop, and an out-of-order probe
    behind the watermark still answers exactly."""
    q = RequestQueue(cap=8)
    for i in range(3):
        q.submit(Request(rid=i, prompt=np.ones(4), arrival_s=float(i)))
    assert q.depth(now=1.5) == 2
    assert q.pop_ready(now=1.5).rid == 0
    assert q.depth(now=1.5) == 1
    assert q.pop_ready(now=1.5).rid == 1
    assert q.pop_ready(now=1.5) is None  # rid 2 hasn't arrived
    assert q.next_arrival() == 2.0
    assert q.depth(now=2.5) == 1
    # a submission at/behind the watermark is immediately arrived
    q.submit(Request(rid=3, prompt=np.ones(4), arrival_s=2.5))
    assert q.depth(now=2.5) == 2
    assert q.pending == 2 and len(q) == 2
    # non-monotonic probe: exact answer, not the cached watermark count
    assert q.depth(now=0.0) == 0
    assert q.depth(now=2.0) == 1


def test_admission_gate_and_stamp_share_one_timestamp(serving_engine):
    """_admit_ready reads the clock once per admission: the pop_ready gate
    value IS the on_admit stamp (a clock that advances on every read would
    otherwise skew queue_s/TTFT)."""
    eng, tp, dp = serving_engine

    class StutterClock(VirtualClock):
        def now(self):  # every read advances: a double read is detectable
            t, self._t = self._t, self._t + 1e-3
            return t

    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=StutterClock())
    gates = []
    orig = rt.queue.pop_ready
    rt.queue.pop_ready = lambda now: (gates.append(now), orig(now))[1]
    rt.submit(Request(rid=0, prompt=_prompt(2), max_new=4))
    rt.run()
    assert rt.stats.records[0].admitted_s in gates


def test_queue_admission_control():
    q = RequestQueue(cap=3)
    ok = [q.submit(Request(rid=i, prompt=np.ones(4), arrival_s=float(i))) for i in range(5)]
    assert ok == [True, True, True, False, False]
    assert q.submitted == 5 and q.rejected == 2 and len(q) == 3
    # arrival gating: nothing poppable before its arrival time
    assert q.pop_ready(now=-1.0) is None
    assert q.depth(now=1.5) == 2
    r0 = q.pop_ready(now=0.0)
    assert r0.rid == 0  # FIFO
    assert q.next_arrival() == 1.0
    # freed capacity admits again
    assert q.submit(Request(rid=9, prompt=np.ones(4), arrival_s=9.0))
    assert q.pop_ready(now=9.0).rid == 1
    # an already-arrived submission is always orderable: it queues behind
    # everything already here (live submits cannot poison the queue)
    assert q.submit(Request(rid=10, prompt=np.ones(4), arrival_s=0.5))
    assert q.pop_ready(now=9.0).rid == 2  # FIFO by insertion
    # but FUTURE submissions must stay arrival-ordered (trace sanity)
    assert q.submit(Request(rid=11, prompt=np.ones(4), arrival_s=20.0))
    with pytest.raises(ValueError):
        q.submit(Request(rid=12, prompt=np.ones(4), arrival_s=15.0))


def test_burst_trace_invariants(serving_engine):
    """A burst larger than the queue cap: the overflow is shed at the door,
    every admitted request finishes, occupancy never exceeds the slots."""
    eng, tp, dp = serving_engine
    rt = ContinuousBatchingRuntime(
        eng, tp, dp, n_slots=2, clock=VirtualClock(),
        queue=RequestQueue(cap=4),
    )
    reqs = [Request(rid=i, prompt=_prompt(2 * i + 1), arrival_s=0.0, max_new=8)
            for i in range(6)]
    assert rt.submit_trace(reqs) == 4
    assert rt.queue.rejected == 2
    results = rt.run()
    assert sorted(results) == [0, 1, 2, 3]
    assert all(len(v) == 8 for v in results.values())
    assert all(r.finish_s is not None for r in rt.stats.records.values())
    assert max(rt.stats.occupancy_samples) <= 2
    # an ARRIVED prompt that cannot fit the cache budget is rejected at submit()
    assert not rt.submit(Request(rid=99, prompt=np.ones(250, np.int32), arrival_s=0.0))
    assert rt.queue.rejected == 3


def test_overlong_prompt_rejected_at_arrival_not_submit(serving_engine):
    """A too-long prompt with a FUTURE arrival is accepted at submit time and
    shed when it arrives — same live-traffic semantics as the queue cap — so
    submitted/rejected counters reflect offered load, not trace length."""
    eng, tp, dp = serving_engine
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=VirtualClock())
    assert rt.submit(Request(rid=0, prompt=_prompt(3), arrival_s=0.0, max_new=8))
    # deferred: nothing counted against the queue yet
    assert rt.submit(Request(rid=1, prompt=np.ones(250, np.int32), arrival_s=5.0))
    assert rt.queue.submitted == 1 and rt.queue.rejected == 0
    results = rt.run()
    assert sorted(results) == [0]
    # the reject landed when the clock reached arrival_s=5.0
    assert rt.queue.submitted == 2 and rt.queue.rejected == 1
    assert 1 not in rt.stats.records


def test_cap_sheds_on_arrived_backlog_not_trace_length(serving_engine):
    """A long trace with spread-out arrivals never builds a backlog, so a cap
    smaller than the trace sheds nothing (live-traffic admission semantics)."""
    eng, tp, dp = serving_engine
    rt = ContinuousBatchingRuntime(
        eng, tp, dp, n_slots=1, clock=VirtualClock(),
        queue=RequestQueue(cap=2),
    )
    reqs = [Request(rid=i, prompt=_prompt(3 * i + 2), arrival_s=40.0 * i, max_new=8)
            for i in range(5)]  # each finishes in ~8 rounds << 40 between arrivals
    assert rt.submit_trace(reqs) == 5
    results = rt.run()
    assert sorted(results) == [0, 1, 2, 3, 4]
    assert rt.queue.rejected == 0, "cap must shed on arrived backlog, not trace length"


# ---------------------------------------------------------------------------
# per-row stats accounting (engine satellite)
# ---------------------------------------------------------------------------


def test_specstats_per_row_exact(dense_pair):
    """No per-round floor division: emitted_rows[b] == accepted_rows[b] +
    rounds (each row emits its acceptances + 1 bonus every round)."""
    T, D, tp, dp = dense_pair
    eng = SpecEngine(T, D, SpecConfig(bs=8, w=4, c=2, d=2, max_new=12),
                     S_max_t=256, S_max_d=256)
    prompt = (np.arange(16, dtype=np.int32).reshape(2, 8) * 3 + 1) % 128
    out, stats = eng.session(tp, dp).generate(prompt, max_new=12)
    assert stats.emitted_rows.shape == (2,)
    np.testing.assert_array_equal(stats.emitted_rows, stats.accepted_rows + stats.rounds)
    assert all(er >= len(o) for er, o in zip(stats.emitted_rows, out))
    assert stats.emitted == pytest.approx(stats.emitted_rows.mean())
    assert stats.total_emitted == int(stats.emitted_rows.sum())
