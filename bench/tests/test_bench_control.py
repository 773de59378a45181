"""The control of the correctness check: the float32 reference put in the
program's place one precision down (float8 weights, bfloat16 activations)
must read above the configuration's limit, where the reference itself reads
nothing.

On the chip it is read at the cells' own sizes (``calibrate.py readings``).
Here it runs at a size the CPU holds whose depth still lets float8 rounding
move the context-dependent logits: 24 layers of width 512 over the
published 32,256-token vocabulary, with the benchmark's planted weights.
The served tokens are the reference's own greedy continuation of each
prompt, as a sound program serves them."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench import spec
from bench import weights as W
from bench.reference import llama as ref
from bench.tests.smoke import BENCH, LIMIT
from bench.traffic.generate import Item

CONFIG = {"vocab_size": 32256, "hidden_size": 512, "num_hidden_layers": 24,
          "num_attention_heads": 8, "num_key_value_heads": 8, "intermediate_size": 1408,
          "rope_theta": 100000, "rms_norm_eps": 1e-5, "reference": "llama",
          "check": LIMIT, "program": {"bs": 8}, "draft": {}}
LAYOUT = spec.load_layout(BENCH.parent, "llama")
REQUESTS, PROMPT, OUT = 4, 128, 112
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
        _, top = ref.head(ref.hidden(weights, CONFIG, jnp.asarray(seq)), weights, CONFIG,
                          jnp.asarray(seq))
        top = np.asarray(top)[:, P - 1: P + OUT - 1]
        bad = top != seq[:, P:]
        if not bad.any():
            return seq[:, P:]
        for b in np.flatnonzero(bad.any(axis=1)):
            j = int(np.argmax(bad[b]))
            seq[b, P + j] = top[b, j]
            chain(b, P + j + 1)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_reads_above_the_limit(seed):
    V = CONFIG["vocab_size"]
    inv_t, _, keep = W.plant_maps(V, seed, disagree=0.2, free=0.1)
    weights = W.make_fn(LAYOUT, LAYOUT.dims(CONFIG), "bfloat16", 4.0)(
        W.key_for(seed, 10), jnp.asarray(inv_t), jnp.asarray(keep))
    prompts = np.random.default_rng(seed).integers(0, V, (REQUESTS, PROMPT), dtype=np.int32)
    served = _greedy(weights, prompts, np.argsort(inv_t))
    items = [Item(rid, 0.0, prompts[rid], OUT) for rid in range(REQUESTS)]
    results = {rid: served[rid].tolist() for rid in range(REQUESTS)}
    pair = R.Pair(None, None, None, weights, 1, 0)
    out = R.check(pair, CONFIG, MIX, items, results, seed, control=True)
    assert out["requests"] == REQUESTS and out["tokens"] == REQUESTS * OUT
    assert out["short"] == 0 and out["max_gap"] == 0.0
    assert out["control_max_gap"] > out["limit"] == LIMIT["max_gap"]
