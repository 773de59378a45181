"""Fused KV-reorganization kernels (kernels/kv_moves.py) vs the index-based
reference (kernels/ref.kv_move_rows_ref) vs a numpy loop oracle.

The contract: byte-identical moves under parallel-assignment semantics for
overlapping src/dst windows, ``-1`` sources, duplicate masked destinations,
and empty plans; the non-donating variant never mutates its input (the
lookahead's snapshot/rollback contract of core/kv.py); and the whole engine
— solo, the lookahead's commit AND rollback, and 2-replica sharded serving —
emits the same bytes with the fused kernels enabled as the reference path
(or target-only greedy decoding) does.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import greedy_reference
from repro.core import kv as kvm
from repro.core.engine import SpecConfig, SpecEngine
from repro.flags import override_flags
from repro.kernels import ops
from repro.kernels.kv_moves import kv_move_rows_pallas, slot_write_rows_pallas
from repro.kernels.ref import kv_move_rows_ref
from repro.serving import Request, ShardedServingRuntime, VirtualClock


def _loop_oracle(arr, src, dst, mask):
    """Parallel assignment in numpy: all sources read before any write."""
    arr, src, dst, mask = map(np.asarray, (arr, src, dst, mask))
    out = arr.copy()
    act = mask & (src >= 0) & (dst >= 0)
    B, M = src.shape
    for b in range(B):
        for m in range(M):
            if act[b, m]:
                out[:, b, dst[b, m]] = arr[:, b, src[b, m]]
    return out


def _random_plan(rng, B, S, M):
    """Overlapping windows, -1 sources, duplicate destinations among masked
    rows (active destinations stay distinct, as MovePlan guarantees)."""
    src = rng.integers(0, S, size=(B, M)).astype(np.int32)
    src[rng.random((B, M)) < 0.2] = -1
    dst = np.stack([rng.permutation(S)[:M] for _ in range(B)]).astype(np.int32)
    mask = rng.random((B, M)) < 0.7
    # duplicate dsts allowed only where masked off: point them at a masked
    # twin's destination so the drop path is what keeps them out
    for b in range(B):
        off = np.where(~mask[b])[0]
        if len(off) >= 2:
            dst[b, off[0]] = dst[b, off[1]]
    return src, dst, mask


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_ref_matches_loop_oracle(seed):
    rng = np.random.default_rng(seed)
    U, B, S, F, M = 2, 3, 16, 5, 7
    arr = jnp.asarray(rng.normal(size=(U, B, S, F)), jnp.float32)
    src, dst, mask = _random_plan(rng, B, S, M)
    got = kv_move_rows_ref(arr, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(got), _loop_oracle(arr, src, dst, mask))


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_fused_matches_reference(seed):
    """Both kernel variants, interpret mode, byte-identical to the ref."""
    rng = np.random.default_rng(seed)
    U, B, S, F, M = 2, 2, 12, 4, 5
    arr = jnp.asarray(rng.normal(size=(U, B, S, F)), jnp.float32)
    src, dst, mask = _random_plan(rng, B, S, M)
    want = kv_move_rows_ref(arr, jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask))
    active = jnp.asarray((mask & (src >= 0) & (dst >= 0)).astype(np.int32))
    for donate in (False, True):
        got = kv_move_rows_pallas(arr, jnp.asarray(src), jnp.asarray(dst), active,
                                  donate=donate, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_copy_through_preserves_input():
    """The non-donating variant is the zero-copy-snapshot keeper: the input
    buffer must be bit-unchanged after the call, even under jit."""
    rng = np.random.default_rng(0)
    arr = jnp.asarray(rng.normal(size=(1, 1, 8, 3)), jnp.float32)
    before = np.asarray(arr).copy()
    src = jnp.asarray([[0, 1]], jnp.int32)
    dst = jnp.asarray([[4, 5]], jnp.int32)
    act = jnp.ones((1, 2), jnp.int32)
    f = jax.jit(lambda a: kv_move_rows_pallas(a, src, dst, act, donate=False, interpret=True))
    out = f(arr)
    assert not np.array_equal(np.asarray(out), before)  # rows really moved
    np.testing.assert_array_equal(np.asarray(arr), before)  # snapshot intact


def test_empty_move_plans():
    """All-masked plans are no-ops; an M=0 plan short-circuits in ops."""
    rng = np.random.default_rng(1)
    arr = jnp.asarray(rng.normal(size=(2, 1, 6, 3)), jnp.float32)
    src = jnp.asarray([[2, -1]], jnp.int32)
    dst = jnp.asarray([[4, 4]], jnp.int32)
    none = jnp.zeros((1, 2), bool)
    for donate in (False, True):
        got = kv_move_rows_pallas(arr, src, dst, none.astype(jnp.int32),
                                  donate=donate, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(arr))
    np.testing.assert_array_equal(
        np.asarray(kv_move_rows_ref(arr, src, dst, none)), np.asarray(arr))
    empty = jnp.zeros((1, 0), jnp.int32)
    out = ops.kv_move_rows(arr, empty, empty, jnp.zeros((1, 0), bool))
    assert out is arr


def test_apply_moves_flag_paths_identical():
    """kv.apply_moves: fused and reference paths agree byte-for-byte on a
    cache pytree, and non-row leaves / "len" stay untouched on both."""
    rng = np.random.default_rng(2)
    S, M = 16, 6
    cache = {
        "len": jnp.asarray(3, jnp.int32),
        "groups": [({"k": jnp.asarray(rng.normal(size=(2, 1, S, 2, 3)), jnp.float32),
                     "v": jnp.asarray(rng.normal(size=(2, 1, S, 2, 3)), jnp.float32),
                     "ssm": jnp.full((2, 1, 4), 7.0)},)],
    }
    src, dst, mask = _random_plan(rng, 1, S, M)
    args = (jnp.asarray(src), jnp.asarray(dst), jnp.asarray(mask))
    ref = kvm.apply_moves(cache, *args)
    with override_flags(use_pallas_kv_moves=True):
        fused = kvm.apply_moves(cache, *args)
        fused_d = kvm.apply_moves(cache, *args, donate=True)
    for got in (fused, fused_d):
        for key in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(got["groups"][0][0][key]),
                np.asarray(ref["groups"][0][0][key]))
        np.testing.assert_array_equal(np.asarray(got["groups"][0][0]["ssm"]), 7.0)
        assert int(got["len"]) == 3


# ---------------------------------------------------------------------------
# slot lifecycle: one fused launch vs the per-leaf XLA path
# ---------------------------------------------------------------------------


def _toy_cache(rng, B, S):
    return {
        "len": jnp.zeros((), jnp.int32),
        "groups": [({"k": jnp.asarray(rng.normal(size=(2, B, S, 2, 3)), jnp.float32),
                     "v": jnp.asarray(rng.normal(size=(2, B, S, 2, 3)), jnp.float32),
                     "state": jnp.asarray(rng.normal(size=(1, B, 4)), jnp.float32)},)],
    }


def test_install_and_zero_slot_fused_match_xla():
    rng = np.random.default_rng(3)
    big, one = _toy_cache(rng, 3, 8), _toy_cache(rng, 1, 8)
    want_inst = kvm.install_slot(big, one, 1)
    want_zero = kvm.zero_slot(big, 2)
    with override_flags(use_pallas_kv_moves=True):
        got_inst = kvm.install_slot(big, one, 1)
        got_zero = kvm.zero_slot(big, 2)
    for got, want in ((got_inst, want_inst), (got_zero, want_zero)):
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_slot_write_rows_traced_slot_and_dtype_fallback():
    rng = np.random.default_rng(4)
    big, one = _toy_cache(rng, 3, 8), _toy_cache(rng, 1, 8)
    with override_flags(use_pallas_kv_moves=True):
        # traced slot: one jit covers every slot index (the engine contract)
        f = jax.jit(kvm.install_slot, donate_argnums=(0,))
        got = f(jax.tree.map(jnp.copy, big), one, jnp.asarray(2, jnp.int32))
        want = kvm.install_slot(big, one, 2)
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # dtype mismatch: the fused kernel declines, the XLA path casts
        one16 = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.ndim > 0 else x, one)
        assert ops.slot_write_rows(
            jax.tree.leaves(big["groups"]), jax.tree.leaves(one16["groups"]), 0) is None
        got = kvm.install_slot(big, one16, 0)
        np.testing.assert_array_equal(
            np.asarray(got["groups"][0][0]["k"][:, 0]),
            np.asarray(one16["groups"][0][0]["k"][:, 0].astype(jnp.float32)))


def test_slot_write_rows_pallas_rejects_bad_leaves():
    a = jnp.zeros((2, 3, 4))
    with pytest.raises(ValueError):
        slot_write_rows_pallas([a], [jnp.zeros((2, 2, 4))], 0, interpret=True)
    with pytest.raises(ValueError):
        slot_write_rows_pallas([], [], 0, interpret=True)


# ---------------------------------------------------------------------------
# engine surfaces: fused path byte-identical to the reference path
# ---------------------------------------------------------------------------

ECFG = dict(bs=4, w=2, c=2, d=1, n_cap=16, max_new=8)


def _prompt(k, P=8):
    return ((np.arange(1, P + 1) * k + 3) % 128).astype(np.int32)


@pytest.fixture(scope="module")
def fused_engines(dense_pair):
    T, D, tp, dp = dense_pair

    def mk(tgt, dr, **kw):
        return SpecEngine(tgt, dr, SpecConfig(**ECFG, **kw), S_max_t=256, S_max_d=256)

    return {"ref": mk(T, D), "fused": mk(T, D), "fused_self": mk(T, T),
            "sharded_ref": mk(T, D), "sharded_fused": mk(T, D)}, tp, dp


def test_solo_generate_fused_identical(fused_engines):
    e, tp, dp = fused_engines
    prompt = _prompt(3).reshape(1, -1)
    out_ref, _ = e["ref"].session(tp, dp).generate(prompt)
    with override_flags(use_pallas_kv_moves=True):
        out_fused, _ = e["fused"].session(tp, dp).generate(prompt)
    assert out_fused == out_ref


def test_async_commit_and_rollback_fused_identical(fused_engines):
    """With the fused kernels on, the round's commit path (self-draft,
    lookahead adopted) AND the rollback path (sabotaged predictor, reconcile
    re-roots the retained snapshot) both stay byte-identical to target-only
    greedy decoding — i.e. the copy-through kernel really preserved the
    snapshot and the donating kernel really moved the rows the reference
    would have."""
    e, tp, dp = fused_engines
    prompt = _prompt(5).reshape(1, -1)
    eng = e["fused_self"]
    out_ref = greedy_reference(eng.target, tp, prompt, ECFG["max_new"])
    with override_flags(use_pallas_kv_moves=True):
        out_commit, st = eng.session(tp, tp).generate(prompt)
        assert out_commit == out_ref
        assert st.spec_commits > 0, "commit path never exercised"
        real = eng._predict
        try:  # force the rollback branch every round
            eng._predict = lambda *a: (
                lambda p: (p[0], p[1], jnp.full_like(p[2], -1)))(real(*a))
            out_rb, st = eng.session(tp, tp).generate(prompt)
        finally:
            eng._predict = real
        assert out_rb == out_ref
        assert st.spec_rounds > 0 and st.spec_commits == 0


def test_sharded_serving_fused_identical(fused_engines):
    """2-replica sharded serving (slot install/zero through the fused
    single-launch writer, per-round moves through the fused kernels) emits
    exactly the reference fleet's bytes."""
    e, tp, dp = fused_engines
    reqs = [Request(rid=i, prompt=_prompt(i + 2), arrival_s=0.4 * i, max_new=6)
            for i in range(3)]

    def serve(eng):
        rt = ShardedServingRuntime([eng] * 2, tp, dp, n_slots=2, clock=VirtualClock())
        rt.submit_trace(Request(rid=r.rid, prompt=r.prompt, arrival_s=r.arrival_s,
                                max_new=r.max_new) for r in reqs)
        return rt.run()

    ref = serve(e["sharded_ref"])
    with override_flags(use_pallas_kv_moves=True):
        fused = serve(e["sharded_fused"])
    assert fused == ref and sorted(fused) == [0, 1, 2]
