"""The control of the correctness check on DeepSeek-V2
(``bench/reference/deepseek_v2.py``, ``control=True``: float8 weights and
activations) must read above ``dsv2lite-l7_draft1.3b``'s limit, where the
reference itself reads nothing.

On the chip it is read at the cell's own size (PERF.md section 2).  Here it
runs at a size the CPU holds: the cell's seven layers (one dense, six of 64
routed experts, top 6, unnormalised gates, two shared) and its rotary and
attention shapes, at width 512 with experts of width 352 (the cell's ratio)
over a 32,768-token vocabulary, with the benchmark's planted weights.  The
served tokens are the reference's own greedy continuation of each prompt, as
a sound program serves them."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench import spec
from bench import weights as W
from bench.tests import smoke
from bench.traffic.generate import Item

CELL = json.loads((smoke.BENCH / "configs" / "dsv2lite-l7_draft1.3b.json").read_text())
CONFIG = dict(CELL, hidden_size=512, intermediate_size=2736, moe_intermediate_size=352,
              vocab_size=32768, draft={})
LAYOUT = spec.load_layout(smoke.BENCH.parent, "deepseek_v2")
REF = spec.load_reference(smoke.BENCH.parent, "deepseek_v2")
REQUESTS, PROMPT, OUT = 3, 96, 64
MIX = {"prompt_buckets": [PROMPT], "output": {"kind": "fixed", "tokens": OUT},
       "check_requests": REQUESTS}


def _greedy(weights, prompts, pi):
    """The reference's greedy continuation: start from the planted chain and
    correct each row's first position where the reference disagrees, until
    none does (the positions before it are final)."""
    P = prompts.shape[1]
    seq = np.concatenate([prompts, np.zeros((len(prompts), OUT), np.int32)], 1)

    def chain(b, start):
        for i in range(start, P + OUT):
            seq[b, i] = pi[seq[b, i - 1]]

    for b in range(len(seq)):
        chain(b, P)
    while True:
        _, top = REF.head(REF.hidden(weights, CONFIG, jnp.asarray(seq)), weights, CONFIG,
                          jnp.asarray(seq))
        top = np.asarray(top)[:, P - 1: P + OUT - 1]
        bad = top != seq[:, P:]
        if not bad.any():
            return seq[:, P:]
        for b in np.flatnonzero(bad.any(axis=1)):
            j = int(np.argmax(bad[b]))
            seq[b, P + j] = top[b, j]
            chain(b, P + j + 1)


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_reads_above_the_limit(seed):
    V, a = CONFIG["vocab_size"], CONFIG["assumed"]
    inv_t, _, keep = W.plant_maps(V, seed, disagree=1 - a["draft_agreement"],
                                  free=a["free_share"])
    weights = W.make_fn(LAYOUT, LAYOUT.dims(CONFIG), "bfloat16", a["logit_scale"])(
        W.key_for(seed, 10), jnp.asarray(inv_t), jnp.asarray(keep))
    prompts = np.random.default_rng(seed).integers(0, V, (REQUESTS, PROMPT), dtype=np.int32)
    served = _greedy(weights, prompts, np.argsort(inv_t))
    items = [Item(rid, 0.0, prompts[rid], OUT) for rid in range(REQUESTS)]
    results = {rid: served[rid].tolist() for rid in range(REQUESTS)}
    pair = R.Pair(None, None, None, weights, 1, 0)
    out = R.check(pair, CONFIG, MIX, items, results, seed, control=True,
                  root=smoke.BENCH.parent)
    assert out["requests"] == REQUESTS and out["tokens"] == REQUESTS * OUT
    assert out["short"] == 0 and out["max_gap"] == 0.0
    assert out["control_max_gap"] > out["limit"] == CELL["check"]["max_gap"]
