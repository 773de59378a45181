"""The pipelined round (``EngineSession.dispatch_verify`` /
``draft_next_tree`` / ``reconcile``).

The contract under test: the pipeline changes WHEN draft work happens (round
N+1's tree is drafted while round N verifies), never which tokens verify
emits — so at temperature 0 every surface (solo generate, continuous
batching, the 2-replica sharded fleet) equals target-only greedy decoding,
whether the lookahead seed commits or is rolled back.  Plus the reconcile
rollback itself: a forced rejected seed must take the snapshot + re-root
path and emit what an untampered session emits, and the traced run must
show draft work genuinely overlapping the open verify window.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import greedy_reference
from repro.core.engine import SpecConfig, SpecEngine, SpecStats
from repro.obs import Tracer, phase_breakdown
from repro.serving import (
    ContinuousBatchingRuntime,
    Request,
    ShardedServingRuntime,
    VirtualClock,
)

CFG = dict(bs=8, w=4, c=2, d=2, n_cap=64, max_new=24)


def _prompt(k, P=8):
    return ((np.arange(1, P + 1) * k + 3) % 128).astype(np.int32)


@pytest.fixture(scope="module")
def engines(dense_pair):
    """Self-draft (draft == target, so the lookahead seed should usually
    commit) and independent-draft (tiny random draft disagrees with the
    target, so reconcile rolls back nearly every round) engines."""
    T, D, tp, dp = dense_pair

    def mk(tgt, dr):
        return SpecEngine(tgt, dr, SpecConfig(**CFG), S_max_t=256, S_max_d=256)

    return {"self": mk(T, T), "td": mk(T, D)}, tp, dp


def _greedy(eng, tp, prompt, n):
    return greedy_reference(eng.target, tp, prompt, n)


# ---------------------------------------------------------------------------
# solo generate: commit path and fallback path both exact
# ---------------------------------------------------------------------------


def test_solo_self_draft_identical_and_commits(engines):
    """Self-draft: predictions hold, so the pre-drafted lookahead tree is
    adopted (spec_commits > 0) and outputs still equal greedy decoding."""
    e, tp, dp = engines
    prompt = _prompt(3).reshape(1, -1)
    out, st = e["self"].session(tp, tp).generate(prompt)
    assert out == _greedy(e["self"], tp, prompt, CFG["max_new"])
    assert st.spec_rounds == st.rounds > 0
    assert st.spec_commits > 0, "self-draft lookahead seed never committed"


def test_solo_independent_draft_identical(engines):
    """Independent tiny draft: the target disagrees, the seed is rejected,
    reconcile rolls back nearly every round — bytes still equal greedy."""
    e, tp, dp = engines
    prompt = _prompt(5).reshape(1, -1)
    out, st = e["td"].session(tp, dp).generate(prompt)
    assert out == _greedy(e["td"], tp, prompt, CFG["max_new"])
    assert st.spec_commits < st.rounds
    assert st.spec_rounds == st.rounds > 0


@pytest.mark.parametrize("B", [1, 2], ids=["B1", "B2"])
def test_forced_rejection_every_round_still_identical(engines, B):
    """Sabotage the predictor so the seed can never match (a real bonus
    token is always >= 0): every round must take the rollback path and the
    output must not change by a byte, for one row and for two."""
    e, tp, dp = engines
    eng = e["self"]
    prompt = np.stack([_prompt(7 + b) for b in range(B)])

    real = eng._predict
    try:
        eng._predict = lambda *a: (lambda p: (p[0], p[1], jnp.full_like(p[2], -1)))(real(*a))
        out, st = eng.session(tp, tp).generate(prompt)
    finally:
        eng._predict = real
    assert out == _greedy(eng, tp, prompt, CFG["max_new"])
    assert st.spec_rounds > 0 and st.spec_commits == 0


# ---------------------------------------------------------------------------
# reconcile unit test: a rejected lookahead seed, forced at the RoundInFlight
# ---------------------------------------------------------------------------


def test_reconcile_rolls_back_rejected_seed(engines):
    """Drive the phase API by hand beside an untampered session: tamper
    each round's prediction so reconcile MUST reject the lookahead and
    re-root from the retained snapshot — per-round results stay
    identical."""
    e, tp, dp = engines
    eng = e["self"]
    prompt = _prompt(4).reshape(1, -1)
    ref = eng.session(tp, tp)
    ref.state = eng._prefill_state(tp, tp, prompt)
    sess = eng.session(tp, tp)
    sess.state = eng._prefill_state(tp, tp, prompt)

    for _ in range(3):
        rif = sess.begin_round()
        pa, pn, pb = rif.pred
        rif.pred = (pa, pn, jnp.full_like(pb, -1))  # seed can never match
        st = SpecStats()
        got = sess.reconcile(rif, stats=st)
        assert st.spec_commits == 0  # the rollback branch really ran
        want = ref.step()
        np.testing.assert_array_equal(got.n_emitted, want.n_emitted)
        np.testing.assert_array_equal(got.n_accepted, want.n_accepted)
        np.testing.assert_array_equal(got.emitted, want.emitted)


def test_dispatch_while_in_flight_is_an_error(engines):
    """The donated-state discipline: a second dispatch (or admit/release)
    before reconcile must fail loudly, not corrupt the round."""
    e, tp, dp = engines
    sess = e["self"].session(tp, tp, n_slots=1)
    sess.admit_slot(0, _prompt(2))
    rif = sess.begin_round()
    with pytest.raises(RuntimeError, match="in flight"):
        sess.dispatch_verify()
    with pytest.raises(RuntimeError, match="in flight"):
        sess.admit_slot(0, _prompt(3))
    sess.reconcile(rif)  # leave the module-scoped fixture quiescent


# ---------------------------------------------------------------------------
# serving: continuous and 2-replica sharded, byte-identical to greedy
# ---------------------------------------------------------------------------


def _serve(rt, reqs):
    rt.submit_trace(Request(rid=r.rid, prompt=r.prompt, arrival_s=r.arrival_s,
                            max_new=r.max_new) for r in reqs)
    return rt.run()


def _greedy_each(eng, tp, reqs):
    return {r.rid: _greedy(eng, tp, r.prompt.reshape(1, -1), r.max_new)[0] for r in reqs}


def test_continuous_async_matches_lockstep(engines):
    """Continuous batching over a self-draft engine (lookaheads commit):
    every request emits its target-only greedy stream."""
    e, tp, dp = engines
    reqs = [Request(rid=i, prompt=_prompt(i + 1, P=8 + 4 * (i % 2)),
                    arrival_s=0.5 * i, max_new=12) for i in range(4)]
    got = _serve(ContinuousBatchingRuntime(
        e["self"], tp, tp, n_slots=2, clock=VirtualClock()), reqs)
    assert got == _greedy_each(e["self"], tp, reqs)


def test_sharded_async_matches_lockstep(engines):
    """Two replicas over an independent draft (lookaheads roll back):
    every request emits its target-only greedy stream."""
    e, tp, dp = engines
    reqs = [Request(rid=i, prompt=_prompt(i + 2), arrival_s=0.4 * i, max_new=10)
            for i in range(4)]
    got = _serve(ShardedServingRuntime(
        [e["td"]] * 2, tp, dp, n_slots=2, clock=VirtualClock()), reqs)
    assert got == _greedy_each(e["td"], tp, reqs)


# ---------------------------------------------------------------------------
# the trace proves the pipeline: draft under the open verify window
# ---------------------------------------------------------------------------


def test_traced_overlap_async_nonzero_lockstep_zero(engines):
    e, tp, dp = engines
    reqs = [Request(rid=i, prompt=_prompt(i + 1), arrival_s=0.0, max_new=10)
            for i in range(2)]
    tracer = Tracer()
    _serve(ContinuousBatchingRuntime(
        e["self"], tp, tp, n_slots=2, clock=VirtualClock(), tracer=tracer), reqs)
    asy = phase_breakdown(tracer)
    # structural overlap assertions are deterministic; the hard >=0.95
    # coverage gate lives in test_obs + the CI smoke, where rounds are long
    # enough not to flake under CPU contention — here just sanity-check it
    assert asy["coverage_mean"] > 0.5
    assert asy["overlap_draft_verify_s"] > 0.0
    assert asy["phase_s"]["draft_lookahead"] > 0.0
    # the whole point: less draft time serialized on the critical path
    assert asy["draft_serialized_frac"] < asy["draft_frac"]
