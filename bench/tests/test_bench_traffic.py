"""The traffic generator: seeded determinism, buckets only, the gamma gaps'
coefficient of variation, the clipped lognormal, the same work for every
seed."""

import json
import statistics

import numpy as np
import pytest

from bench.tests.smoke import BENCH
from bench.traffic.generate import cache_rows, make_items, max_output, n_requests

MIXES = {name: json.loads((BENCH / "traffic" / f"{name}.json").read_text())
         for name in ("single", "chat")}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_inputs(name):
    a = make_items(MIXES[name], 32256, BIG_SEED, 45)
    b = make_items(MIXES[name], 32256, BIG_SEED, 45)
    assert [(i.rid, i.due_s, i.max_new) for i in a] == [(i.rid, i.due_s, i.max_new) for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = make_items(MIXES[name], 32256, BIG_SEED + 1, 45)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", sorted(MIXES))
def test_prompts_come_from_the_buckets_only(name):
    mix = MIXES[name]
    items = make_items(mix, 32256, 7, 45)
    assert {i.prompt.size for i in items} <= set(mix["prompt_buckets"])
    assert all(0 <= i.prompt.min() and i.prompt.max() < 32256 for i in items)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_gets_the_same_work(name):
    mix = MIXES[name]
    runs = [make_items(mix, 32256, s, 45) for s in (1, 2, 3)]
    for size in (lambda i: i.prompt.size, lambda i: i.max_new):
        shapes = [sorted(map(size, items)) for items in runs]
        assert shapes[0] == shapes[1] == shapes[2]
    if mix["loop"] == "open":
        # the same gaps (the last one runs to the window's end)
        gaps = [np.sort(np.diff([i.due_s for i in items] + [45.0])) for items in runs]
        assert np.allclose(gaps[0], gaps[1]) and np.allclose(gaps[0], gaps[2])


@pytest.mark.parametrize("name", sorted(MIXES))
def test_the_seed_draws_only_the_prompt_tokens(name):
    a, b = (make_items(MIXES[name], 32256, s, 45) for s in (BIG_SEED, 5))
    schedule = [[(i.due_s, i.prompt.size, i.max_new) for i in items] for items in (a, b)]
    assert schedule[0] == schedule[1]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_open_loop_fills_the_window_at_the_rate():
    mix = MIXES["chat"]
    items = make_items(mix, 32256, 11, 45)
    due = [i.due_s for i in items]
    assert len(items) == n_requests(mix, 45) == round(mix["rate_rps"] * 45)
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 45


def test_gamma_gaps_have_the_stated_cv():
    mix = dict(MIXES["chat"], rate_rps=10.0)
    due = [i.due_s for i in make_items(mix, 100, 3, 2000)]
    gaps = np.diff(due)
    cv = gaps.std() / gaps.mean()
    assert abs(cv - mix["cv"]) < 0.25


def test_lognormal_outputs_are_clipped_with_the_stated_median():
    mix = dict(MIXES["chat"], rate_rps=10.0)
    outs = [i.max_new for i in make_items(mix, 100, 3, 1000)]
    spec = mix["output"]
    assert min(outs) >= spec["min"] and max(outs) <= spec["max"]
    assert min(outs) == spec["min"] and max(outs) == spec["max"]  # both clips bite
    assert abs(statistics.median(outs) - spec["median"]) <= 4


def test_closed_loop_single_stream():
    mix = MIXES["single"]
    items = make_items(mix, 32256, 5, 45)
    assert len(items) == mix["queue"] and all(i.max_new == 512 for i in items)
    assert mix["slots"] == mix["clients"] == 1
    # the smallest cache that holds 512 + 512 and the engine's 2 * bs headroom
    assert cache_rows(mix, bs=8) == 512 + 512 + 16 and max_output(mix) == 512
