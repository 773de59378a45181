"""The re-root's fused prefix fill and first regrowth expansion
(``SpecEngine._fill_grow``, traced as ``jit_fill_prefix``).

One draft forward over the fill slots and the leaves must grow the same tree,
and leave the same draft cache, as a separate prefix fill followed by a plain
expansion: the fill rows are written before the leaves attend, and the leaves'
prefix mask covers them.  Checked in float32 on the CPU on an empty fill, a
fill of several tokens and the rolled-back re-root that ``reconcile`` runs;
and, for a MoE draft, that the padding fill slots take nothing from the
leaves."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.tree import select_leaves
from repro.core.engine import SpecConfig, SpecEngine
from repro.models.api import make_model

CFG = dict(bs=8, w=4, c=2, d=2, n_cap=64, max_new=24)
PROMPT = ((np.arange(1, 9) * 5 + 3) % 128).astype(np.int32).reshape(1, -1)


@pytest.fixture(scope="module")
def engines(dense_pair):
    """An engine over the independent draft, and one whose draft is the
    target itself, so its rounds accept tokens."""
    T, D, tp, dp = dense_pair
    return {"independent": (SpecEngine(T, D, SpecConfig(**CFG), S_max_t=256, S_max_d=256), D, dp),
            "self": (SpecEngine(T, T, SpecConfig(**CFG), S_max_t=256, S_max_d=256), T, tp)}, tp


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _rerooted(eng, tp, dp, strip_kv: bool, keep_subtree: bool = False):
    """Grow a tree three levels deep, then re-root it on the path to its
    deepest expanded node, accepting a child of that node as the bonus.
    ``strip_kv`` drops the path's KV rows from the tree first, so each
    accepted token has to be filled.  ``keep_subtree`` grows one level more
    and accepts, as the bonus, the expanded node with the most descendants,
    so the re-rooted tree keeps several leaves."""
    state = eng._prefill_state(tp, dp, PROMPT)
    tr, dcache = state.tr, state.dcache
    for _ in range(3 if keep_subtree else 2):
        tr, dcache = eng._expand(dp, tr, dcache)
    h = jax.tree.map(lambda x: np.asarray(x)[0], jax.device_get(tr))
    live = np.flatnonzero(h.valid & h.expanded & (np.arange(h.valid.size) > 0))
    if keep_subtree:
        def n_desc(i):
            kids = np.flatnonzero(h.valid & (h.parent == i))
            return len(kids) + sum(n_desc(k) for k in kids)

        child = max((i for i in live if h.depth[i] >= 2), key=n_desc)
        last = int(h.parent[child])
    else:
        last = int(live[np.argmax(h.depth[live])])
        child = int(np.flatnonzero(h.valid & (h.parent == last))[0])
    path = [last]
    while h.parent[path[0]] > 0:
        path.insert(0, int(h.parent[path[0]]))
    if strip_kv:
        kv = tr.kv_row.at[0, np.asarray(path)].set(-1)
        tr = tr._replace(kv_row=kv)
    bs = eng.cfg.bs
    node_ids = np.zeros((1, bs), np.int32)
    node_ids[0, :len(path)] = path
    acc_pos = np.full((1, bs), -1, np.int32)
    acc_pos[0, :len(path)] = np.arange(len(path))
    tr, move, fill = eng._spec_reroot(tr, jnp.asarray(node_ids), jnp.asarray(acc_pos),
                                      jnp.asarray([len(path)], jnp.int32),
                                      jnp.asarray([h.tokens[child]], jnp.int32))
    dcache = eng._spec_kv_move(dcache, move.src, move.dst, move.mask)
    return tr, dcache, fill


def _rolled_back(eng, tp, dp):
    """The (tree, cache, fill) that ``reconcile`` hands the fused program
    when the lookahead's seed is rejected.  The planned nodes' KV rows are
    dropped from the rollback tree, so each accepted token has to be
    filled."""
    seen = []
    real = eng._fill_grow

    def spy(params, tr, dcache, fill):
        seen.append(_copy((tr, dcache, fill)))
        return real(params, tr, dcache, fill)

    sess = eng.session(tp, dp)
    sess.state = eng._prefill_state(tp, dp, PROMPT)
    rif = sess.begin_round()
    pa, pn, pb = rif.pred
    rif.pred = (pa, pn, jnp.full_like(pb, -1))  # the seed can never match
    tr, dcache = rif.snapshot
    planned = rif.plan.node_ids[:, 1:]  # slot 0 is the root
    rif.snapshot = (tr._replace(kv_row=jax.vmap(lambda r, i: r.at[i].set(-1))(tr.kv_row, planned)),
                    dcache)
    eng._fill_grow = spy
    try:
        res = sess.reconcile(rif)
    finally:
        eng._fill_grow = real
    assert len(seen) == 1  # reconcile rolled back and re-rooted once
    assert int(res.n_accepted[0]) > 0
    return seen[0]


def _assert_same_growth(tr, ref, got):
    """``got`` grew ``tr`` into the same tree as ``ref`` (float fields within
    1e-5) and left the same draft cache within 1e-5."""
    (ref_tr, ref_cache), (got_tr, got_cache) = ref, got
    ref_tr, got_tr = jax.device_get((ref_tr, got_tr))
    for name in ref_tr._fields:
        want, have = np.asarray(getattr(ref_tr, name)), np.asarray(getattr(got_tr, name))
        if want.dtype.kind == "f":
            np.testing.assert_allclose(have, want, rtol=0, atol=1e-5, err_msg=name)
        else:
            np.testing.assert_array_equal(have, want, err_msg=name)
    assert int(got_tr.n_nodes[0]) > int(np.asarray(tr.n_nodes)[0])  # it grew
    for want, have in zip(jax.tree.leaves(ref_cache), jax.tree.leaves(got_cache)):
        np.testing.assert_allclose(np.asarray(have), np.asarray(want), rtol=0, atol=1e-5)


@pytest.mark.parametrize("case", ["empty_fill", "several_filled", "reconcile_rollback"])
def test_fused_fill_grows_what_fill_then_expand_grows(engines, case):
    engines, tp = engines
    eng, D, dp = engines["self" if case == "reconcile_rollback" else "independent"]
    if case == "reconcile_rollback":
        tr, dcache, fill = _rolled_back(eng, tp, dp)
    else:
        tr, dcache, fill = _rerooted(eng, tp, dp, strip_kv=case == "several_filled")
    n_fill = int(np.asarray(fill.mask).sum())
    assert (n_fill == 0) if case == "empty_fill" else (n_fill >= 2)

    # by hand: the prefix fill as its own draft forward, then a plain expansion
    cols = jnp.arange(eng.S_max_d, dtype=jnp.int32)
    fmask = (cols[None, None, :] <= fill.rows[:, :, None]) & fill.mask[:, :, None]
    _, ref_cache = D.spec_forward(dp, _copy(dcache), fill.tokens, fill.positions,
                                  fill.rows, fmask)
    ref_tr, ref_cache = eng._expand(dp, _copy(tr), ref_cache)

    got_tr, got_cache = eng._fill_grow(dp, _copy(tr), _copy(dcache), fill)
    _assert_same_growth(tr, (ref_tr, ref_cache), (got_tr, got_cache))


def test_fused_fill_serves_moe_leaves_first():
    """A Mixtral-shaped draft (8 experts, top-2).  Its dispatch is dropless,
    so a leaf's logits do not depend on what else shares the call: on an
    empty fill the eight fill slots are all padding and route nowhere, and
    the fused program must grow exactly what a plain expansion of the
    leaves alone grows, with the same engine."""
    cfg = dataclasses.replace(get_config("mixtral-8x22b", smoke=True), n_experts=8)
    M = make_model(cfg)
    mp = M.init(jax.random.PRNGKey(2))
    mp["lm_head"].value = mp["lm_head"].value * 4.0
    eng = SpecEngine(M, M, SpecConfig(**CFG), S_max_t=256, S_max_d=256)

    tr, dcache, fill = _rerooted(eng, mp, mp, strip_kv=False, keep_subtree=True)
    assert not np.asarray(fill.mask).any()  # nothing to fill: the slots are padding
    _, leaf_valid = jax.vmap(lambda t: select_leaves(t, eng.cfg.w))(tr)
    assert int(np.asarray(leaf_valid).sum()) >= 2
    ref_tr, ref_cache = eng._expand(mp, _copy(tr), _copy(dcache))
    got_tr, got_cache = eng._fill_grow(mp, _copy(tr), _copy(dcache), fill)
    _assert_same_growth(tr, (ref_tr, ref_cache), (got_tr, got_cache))
