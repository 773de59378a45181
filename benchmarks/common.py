"""Shared benchmark plumbing: model builders, time models, CSV output.

Two measurement regimes on this CPU-only container (each benchmark states
which it uses):
  measured — wall-clock of the real engine/model on CPU smoke configs
             (engine dynamics: compression ratios, acceptance, schedules);
  derived  — analytic roofline model with TPU v5e constants fed by config
             shapes and dry-run artifacts (absolute per-op/per-inference
             times, where CPU wall-clock would be meaningless).
"""

from __future__ import annotations

import csv
import os

import jax
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

# TPU v5e-class chip model (assignment constants)
PEAK_FLOPS = 197e12  # bf16/int8-dequant MXU
HBM_BW = 819e9
LINK_BW = 50e9
AR_BASE = 3e-6  # software latency floor of one small all-reduce
ICI_HOP = 0.8e-6  # per-hop ICI latency (ring all-reduce: 2(tp-1) hops)
OP_OVERHEAD = 1.5e-6  # per fused-op dispatch floor at bs<=16 (latency regime)


def write_csv(name: str, header, rows):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def write_json(name: str, payload: dict):
    """Structured BENCH JSON next to the CSVs (sections the perf trajectory
    tracks, e.g. serving.json's ``phase_breakdown``)."""
    import json

    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def build_pair(target_arch="qwen2.5-14b", draft_layers=2, seed=0, peak=4.0):
    """(target, draft) smoke models sharing a vocab; draft = narrow target."""
    import dataclasses

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models.api import make_model

    cfgT = get_config(target_arch, smoke=True)
    cfgD = dataclasses.replace(cfgT, name=cfgT.name + "-draft", n_layers=draft_layers,
                               d_model=max(32, cfgT.d_model // 2),
                               n_heads=max(2, cfgT.n_heads // 2) if cfgT.n_heads else 0,
                               n_kv_heads=max(1, cfgT.n_kv_heads // 2) if cfgT.n_kv_heads else 0,
                               d_ff=max(32, cfgT.d_ff // 2))
    T, D = make_model(cfgT), make_model(cfgD)
    tp = T.init(jax.random.PRNGKey(seed))
    dp = D.init(jax.random.PRNGKey(seed + 1))
    tp["lm_head"].value = tp["lm_head"].value * peak
    dp["lm_head"].value = dp["lm_head"].value * peak
    return cfgT, cfgD, T, D, tp, dp


# -----------------------------------------------------------------------------
# analytic roofline time model (derived regime)
# -----------------------------------------------------------------------------


def infer_time_model(cfg, tp: int, bs: int, context: int, *, weight_bytes: float = 0.5,
                     act_bytes: float = 2.0):
    """Roofline time for ONE forward of ``bs`` tokens at ``context`` length,
    model sharded TP-``tp``.  weight_bytes=0.5 -> int4 AWQ (paper's serving
    precision).  Returns (t_total, parts dict)."""
    n_active = cfg.active_param_count()
    d = cfg.d_model
    n_layers = cfg.n_layers
    kv_heads = max(cfg.n_kv_heads, 1) if cfg.n_heads else 0
    hd = cfg.head_dim or 0

    t_weights = n_active * weight_bytes / tp / HBM_BW
    kv_bytes = 2 * n_layers * context * kv_heads * hd * act_bytes if cfg.n_heads else 0
    t_kv = kv_bytes / tp / HBM_BW
    t_compute = 2.0 * n_active * bs / (tp * PEAK_FLOPS)
    t_attn = 4.0 * bs * context * (cfg.n_heads or 0) * hd * n_layers / (tp * PEAK_FLOPS)

    # two all-reduces per layer of a [bs, d] bf16 activation (latency-bound at
    # small bs: the paper's fused-LL regime); ring bytes + hop/software floors
    ar_bytes = bs * d * act_bytes
    t_coll = 0.0
    if tp > 1:
        t_one = AR_BASE + 2 * (tp - 1) * ICI_HOP + ar_bytes * (tp - 1) / tp / LINK_BW
        t_coll = 2 * n_layers * t_one
    # dispatch floor: ~7 fused ops per layer
    t_disp = 7 * n_layers * OP_OVERHEAD

    t_mem = t_weights + t_kv
    t = max(t_mem, t_compute + t_attn) + t_coll + t_disp
    return t, {
        "t_weights": t_weights, "t_kv": t_kv, "t_compute": t_compute + t_attn,
        "t_coll": t_coll, "t_disp": t_disp, "t_mem": t_mem,
    }
