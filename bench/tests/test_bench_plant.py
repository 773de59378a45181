"""The planted next-token map: what it promises of the maps, and on smoke
shapes through the program: served output equal to target-only greedy
decoding, and more tokens per round than the program's own random pair."""

import numpy as np
import pytest

from bench import run as R
from bench.tests.smoke import MIXES, SMOKE_CONFIG
from bench.traffic.generate import make_items
from bench.weights import plant_maps


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_maps(seed):
    V = 32256
    inv_t, inv_d, keep = plant_maps(V, seed, disagree=0.2, free=0.1)
    pi, pd = np.argsort(inv_t), np.argsort(inv_d)
    assert sorted(pi) == list(range(V)) and sorted(pd) == list(range(V))
    # one cycle through the whole vocabulary
    x, n = pi[0], 1
    while x != 0:
        x, n = pi[x], n + 1
    assert n == V
    D = pi != pd
    assert D.mean() == pytest.approx(0.2, abs=1e-3)
    # every stretch of 10 steps of a chain holds exactly 2 tokens of D
    chain = [0]
    for _ in range(999):
        chain.append(pi[chain[-1]])
    hits = D[np.array(chain)]
    assert all(2 - 2 <= hits[i: i + 10].sum() <= 2 + 2 for i in range(0, 990))
    assert hits.mean() == pytest.approx(0.2, abs=0.02)
    # the free tokens' planted columns are off, and they are tokens of D
    free_x = inv_t[keep == 0]
    assert len(free_x) == pytest.approx(0.1 * V, abs=1) and D[free_x].all()


def test_same_seed_same_maps():
    a, b = plant_maps(1000, 9, 0.2, 0.1), plant_maps(1000, 9, 0.2, 0.1)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.fixture(scope="module")
def served():
    """One smoke engine in float32, serving the same requests with the
    planted weights and with the engine's own random ones."""
    config = dict(SMOKE_CONFIG, program=dict(SMOKE_CONFIG["program"], dtype="float32"))
    mix = dict(MIXES["single-smoke"], output={"kind": "fixed", "tokens": 24})
    eng = R.build(config, mix)
    pair = R.build_pair(eng, config, seed=7)
    items = make_items(mix, config["vocab_size"], 7, 1.0)[:4]
    open_mix = dict(mix, loop="open", slots=2)
    planted = R.serve(pair, open_mix, items, 1.0)
    import jax

    from repro.launch.serve import init_params

    own = R.Pair(eng, init_params(eng.target, jax.random.PRNGKey(0), eng.mesh_target),
                 init_params(eng.draft, jax.random.PRNGKey(1), eng.mesh_draft), None, 1, 0)
    unplanted = R.serve(own, open_mix, items, 1.0)
    return eng, pair, items, planted, unplanted


def test_served_output_equals_greedy_decoding(served):
    from repro.core.engine import greedy_decode

    eng, pair, items, (_, _, _, results, _), _ = served
    for it in items:
        want = greedy_decode(eng.target, pair.tparams, it.prompt[None, :], it.max_new,
                             eng.S_max_t)[0][0].tolist()
        assert results[it.rid] == want


def _tokens_per_round(rt):
    recs = rt.stats.records.values()
    return sum(r.n_tokens for r in recs) / sum(r.n_rounds for r in recs)


def test_planted_pair_emits_more_tokens_per_round(served):
    _, _, _, planted, unplanted = served
    tpr_planted, tpr_own = _tokens_per_round(planted[0]), _tokens_per_round(unplanted[0])
    assert tpr_planted > tpr_own
    assert tpr_planted > 1.5


def test_plant_readings_by_hand():
    agree = np.array([[1, 1, 1, 1, 0, 1, 0, 0]], bool)
    on_map = np.array([[1, 1, 1, 1, 1, 1, 0, 1]], bool)
    valid = np.array([[1, 1, 1, 1, 1, 1, 1, 0]], bool)
    out = R.plant_readings(agree, on_map, valid, depth=3)
    # rounds over the 7 valid positions: 3 accepted + bonus, 0 + bonus, 1 + bonus
    assert out["chain_tokens_per_round"] == pytest.approx(7 / 3)
    assert out["draft_agreement"] == pytest.approx(5 / 7)
    assert out["target_on_map"] == pytest.approx(6 / 7)
