"""Quickstart: asynchronous disaggregated speculative decoding in ~40 lines.

  PYTHONPATH=src python examples/quickstart.py

Builds a small target/draft pair, decodes one prompt with the SwiftSpec
engine in parallel (async) mode, and verifies the output equals plain greedy
decoding — the system's correctness contract."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.engine import SpecConfig, SpecEngine
from repro.models.api import make_model

# 1. models: any two archs sharing a vocab work; here target = qwen smoke,
#    draft = the same weights (a stand-in for a distilled small model)
cfg = get_config("qwen2.5-14b", smoke=True)
model = make_model(cfg)
params = model.init(jax.random.PRNGKey(0))
params["lm_head"].value = params["lm_head"].value * 4.0  # peaked logits

# 2. engine: bs/w/c/d are the paper's knobs (§5.5)
engine = SpecEngine(
    target=model, draft=model,
    cfg=SpecConfig(bs=8, w=4, c=2, d=2, max_new=32),
    S_max_t=256, S_max_d=256,
)

prompt = (np.arange(1, 9, dtype=np.int32) % cfg.vocab_size).reshape(1, 8)
session = engine.session(params, params)  # bound round API (params + state)
out, stats = session.generate(prompt)
print("speculative:", out[0])
print(f"rounds={stats.rounds} compression={stats.compression_ratio:.2f} "
      f"tokens/round={stats.tokens_per_round:.2f}")

# 3. the correctness contract: equality with target-only greedy decoding
lg, cache = jax.jit(lambda p, t: model.prefill(p, tokens=t, S_max=256))(params, jnp.asarray(prompt))
cur = jnp.argmax(lg[:, -1, :], -1)[:, None].astype(jnp.int32)
ref = [int(cur[0, 0])]
step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, 256))
for _ in range(31):
    lg, cache = step(params, cache, cur)
    cur = jnp.argmax(lg[:, -1, :], -1)[:, None].astype(jnp.int32)
    ref.append(int(cur[0, 0]))
assert out[0] == ref, "speculative decoding diverged from greedy!"
print("matches target-only greedy decoding — OK")
