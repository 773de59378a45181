"""Paper Figure 9 — decoding speed vs draft/target GPU allocation.

Regime: MEASURED dynamics + DERIVED schedule.  For each (target tp = x,
draft tp = 8-x) split, the round time comes from the roofline model and the
compression ratio from the measured engine (deeper trees when the draft is
faster, via the paper's d = t_target/t_draft rule).

Claim reproduced: big-target pairs (deepseek-coder-33b, qwen2-72b) prefer
6+2; pairs with a relatively stronger draft prefer 4+4."""

from __future__ import annotations

import numpy as np

from repro.configs.base import ModelConfig
from repro.core.engine import SpecConfig, SpecEngine

from benchmarks.common import build_pair, infer_time_model, write_csv

PAIRS = {
    "dscoder-33b/1.3b": (
        ModelConfig(name="t", n_layers=62, d_model=7168, n_heads=56, n_kv_heads=8,
                    d_ff=19200, vocab_size=32256),
        ModelConfig(name="d", n_layers=24, d_model=2048, n_heads=16, n_kv_heads=16,
                    d_ff=5504, vocab_size=32256),
    ),
    "qwen2-72b/1.5b": (
        ModelConfig(name="t", n_layers=80, d_model=8192, n_heads=64, n_kv_heads=8,
                    d_ff=29568, vocab_size=152064),
        ModelConfig(name="d", n_layers=28, d_model=1536, n_heads=12, n_kv_heads=2,
                    d_ff=8960, vocab_size=151936),
    ),
    "r1-llama-70b/8b": (  # strong 8B draft: more draft compute pays off
        ModelConfig(name="t", n_layers=80, d_model=8192, n_heads=64, n_kv_heads=8,
                    d_ff=28672, vocab_size=128256),
        ModelConfig(name="d", n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8,
                    d_ff=14336, vocab_size=128256),
    ),
}


def ratio_at_depth(T, tp, d):
    eng = SpecEngine(T, T, SpecConfig(bs=8, w=4, c=2, d=max(1, min(d, 6)), max_new=32),
                     512, 512)
    prompt = (np.arange(1, 9, dtype=np.int32) % 100).reshape(1, 8)
    _, st = eng.session(tp, tp).generate(prompt)
    return st.compression_ratio


def run():
    _, _, T, D, tpv, dpv = build_pair()
    rows = []
    best = {}
    # measured compression as a function of achievable tree depth d
    ratio_cache = {d: ratio_at_depth(T, tpv, d) for d in range(1, 7)}
    for pair, (tgt, drf) in PAIRS.items():
        scores = {}
        for x in (2, 4, 6):  # even target TP (paper §5.5)
            t_t, _ = infer_time_model(tgt, x, 8, 512)
            t_d, _ = infer_time_model(drf, 8 - x, 8, 512)
            d = max(1, min(int(t_t / t_d), 6))
            ratio = ratio_cache[d]
            tps = ratio / (max(t_t, d * t_d) + 20e-6)
            scores[x] = tps
            rows.append([pair, x, 8 - x, round(t_t * 1e3, 2), round(t_d * 1e3, 2), d,
                         round(ratio, 2), round(tps, 1)])
        best[pair] = max(scores, key=scores.get)
        print(f"  {pair:20s} " + "  ".join(f"{x}+{8-x}={v:6.1f}t/s" for x, v in scores.items())
              + f"  -> best target tp = {best[pair]}")
    path = write_csv("fig9_allocation.csv",
                     ["pair", "target_tp", "draft_tp", "t_target_ms", "t_draft_ms",
                      "depth_d", "compression", "tokens_per_s"], rows)
    assert best["dscoder-33b/1.3b"] == 6 and best["qwen2-72b/1.5b"] == 6, best
    print(f"  -> 33B/72B targets prefer 6+2 (paper Fig. 9); {path}")
    return path


if __name__ == "__main__":
    run()
