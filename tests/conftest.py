import os
import sys

# NOTE: no XLA_FLAGS here — smoke tests and benches must see ONE device.
# Multi-device tests spawn subprocesses that set the flag themselves.

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

try:  # hypothesis is an optional test dep (requirements-test.txt); without it
    import hypothesis  # noqa: F401
except ImportError:  # the property tests fall back to a deterministic stub
    sys.path.insert(0, os.path.dirname(__file__))
    import _hypothesis_fallback as _hyp

    sys.modules.setdefault("hypothesis", _hyp)
    sys.modules.setdefault("hypothesis.strategies", _hyp.strategies)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.engine import greedy_decode
from repro.models.api import make_model


@pytest.fixture(scope="session")
def dense_pair():
    """(target, draft) small dense models sharing a vocab, peaked logits."""
    cfgT = ModelConfig(name="t", n_layers=3, d_model=64, n_heads=4, n_kv_heads=2,
                       d_ff=128, vocab_size=128)
    cfgD = ModelConfig(name="d", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
                       d_ff=64, vocab_size=128)
    T, D = make_model(cfgT), make_model(cfgD)
    tp = T.init(jax.random.PRNGKey(0))
    dp = D.init(jax.random.PRNGKey(1))
    tp["lm_head"].value = tp["lm_head"].value * 4.0  # peaked greedy chains
    dp["lm_head"].value = dp["lm_head"].value * 4.0
    return T, D, tp, dp


def greedy_reference(model, params, prompt, n, S_max=256):
    """Target-only greedy decoding (the spec-equality oracle), as lists."""
    return greedy_decode(model, params, prompt, n, S_max)[0].tolist()
