"""DeepSeek-V2-Lite as the benchmark's target (``bench/layouts/deepseek_v2.py``,
``bench/reference/deepseek_v2.py``, ``configs/dsv2lite-l7_draft1.3b.json``).

- The program, served through ``EngineSession`` on the benchmark's seeded
  weights at the smoke size, gives the reference's logits after prefill,
  after a decode step through the cache, and for every node of a tree
  verify.
- ``same_model`` holds the program to every published key it runs; the
  program tree has a dense group and a MoE group over the benchmark's own
  arrays; the roofline's counts equal a hand count from the published
  sizes; a smoke cell runs to ``correct`` and reports ``experts_touched``.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.tests import smoke

CONFIG = json.loads((smoke.BENCH / "configs" / "dsv2lite-l7_draft1.3b.json").read_text())
LAYOUT = spec.load_layout(smoke.BENCH.parent, "deepseek_v2")
REF = spec.load_reference(smoke.BENCH.parent, "deepseek_v2")

# the program's smoke shapes of the pair (configs/deepseek_v2_lite*.py), in
# float32 so that no top-k choice of experts is a near-tie that rounding
# could turn (the reference routes in float32)
SMOKE = dict(
    CONFIG, source="test", deployment="test: the program's smoke shapes of DeepSeek-V2-Lite",
    hidden_size=64, intermediate_size=160, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_hidden_layers=3, moe_intermediate_size=32, n_routed_experts=16, vocab_size=256,
    rope_scaling=dict(CONFIG["rope_scaling"], original_max_position_embeddings=64),
    draft=dict(CONFIG["draft"], hidden_size=32, intermediate_size=64, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=256, rope_theta=10000),
    program=dict(CONFIG["program"], smoke=True, dtype="float32"))
MIX = smoke.MIXES["single-smoke"]
# float32 program against the float32 reference at HIGHEST precision: what
# is left is the order of summation (absorbed against materialised latent
# attention, grouped against per-expert matmuls), ~1e-6 of logits of a few
# units; a lower-precision program misses by 1e-2 or more
ATOL = 1e-4


@pytest.fixture(scope="module")
def served():
    from bench import run as R

    eng = R.build(SMOKE, MIX)
    return eng, R.build_pair(eng, SMOKE, 2**31 + 5)


def _ref_logits(pair, tokens):
    h = REF.hidden(pair.tplain, SMOKE, jnp.asarray(tokens))
    x = np.asarray(h, np.float64)
    x = x / np.sqrt((x * x).mean(-1, keepdims=True) + SMOKE["rms_norm_eps"])
    return x @ np.asarray(pair.tplain["lm_head"], np.float64)


def test_prefill_and_cached_decode_match_the_reference(served):
    eng, pair = served
    prompt = (np.arange(1, 17) * 11 % 256).astype(np.int32).reshape(1, -1)
    logits, cache = eng._tprefill(pair.tparams, jnp.asarray(prompt), eng.S_max_t)
    want = _ref_logits(pair, prompt)
    np.testing.assert_allclose(np.asarray(logits), want, atol=ATOL, rtol=0)
    nxt = np.asarray(logits)[:, -1].argmax(-1).astype(np.int32)
    step, _ = eng.target.decode_step(pair.tparams, cache, jnp.asarray(nxt[:, None]), eng.S_max_t)
    want = _ref_logits(pair, np.concatenate([prompt, nxt[:, None]], 1))[:, -1]
    np.testing.assert_allclose(np.asarray(step)[:, 0], want, atol=ATOL, rtol=0)


def test_tree_verify_matches_the_reference(served):
    eng, pair = served
    prompt = (np.arange(1, 21) * 7 % 256).astype(np.int32)
    sess = eng.session(pair.tparams, pair.dparams, n_slots=1)
    sess.admit_slot(0, prompt)
    plan = jax.device_get(sess.state.plan)
    logits, _ = eng.target.spec_forward(pair.tparams, sess.state.tcache, plan.tokens,
                                        plan.positions, plan.rows, plan.mask)
    logits = np.asarray(logits)[0]
    valid, parent, tokens = plan.valid[0], plan.parent_pos[0], plan.tokens[0]
    assert valid.sum() >= 4  # a tree of several nodes
    for j in np.flatnonzero(valid):
        path = [j]
        while parent[path[0]] >= 0:
            path.insert(0, parent[path[0]])
        # slot 0 is the root: the prompt's last token, at its own position
        seq = np.concatenate([prompt[:-1], tokens[path]]).reshape(1, -1)
        np.testing.assert_allclose(logits[j], _ref_logits(pair, seq)[0, -1], atol=ATOL, rtol=0)


def test_the_program_tree_has_a_dense_and_a_moe_group(served):
    eng, pair = served
    groups = pair.tparams["groups"]
    assert len(groups) == 2 and "router" in groups[1][0]["mlp"]
    assert groups[0][0]["attn"]["w_q"].value is pair.tplain["layers"]["dense"]["wq"]
    assert groups[1][0]["mlp"]["shared"]["wd"].value is pair.tplain["layers"]["moe"]["swd"]


@pytest.mark.parametrize("key, value", [
    ("norm_topk_prob", True), ("q_lora_rank", 1536), ("routed_scaling_factor", 16.0),
    ("rope_scaling", None), ("rope_scaling.factor", 4), ("rope_scaling.mscale_all_dim", 1.0),
    ("first_k_dense_replace", 0), ("n_routed_experts", 160), ("kv_lora_rank", 256),
    ("scoring_func", "sigmoid"), ("rms_norm_eps", 1e-5)])
def test_same_model_holds_every_published_key(key, value):
    from repro.launch.serve import serving_configs

    cfgT, _ = serving_configs("deepseek-v2-lite", "deepseek-v2-lite-draft", smoke=False,
                              target_layers=7, dtype="bfloat16")
    LAYOUT.same_model("target", CONFIG, cfgT)  # the configuration as it is
    hf = json.loads(json.dumps(CONFIG))
    if key.startswith("rope_scaling."):
        hf["rope_scaling"][key.split(".")[1]] = value
    else:
        hf[key] = value
    with pytest.raises(ValueError, match="target"):
        LAYOUT.same_model("target", hf, cfgT)


def test_roofline_counts_by_hand():
    r = LAYOUT.roofline(CONFIG)
    d, V, H, kvl = 2048, 102400, 16, 512
    attn = d * H * 192 + d * 576 + kvl * H * 256 + H * 128 * d  # 13,762,560 matrices
    expert = 3 * d * 1408
    dense = attn + 3 * d * 10944 + 2 * d + kvl
    moe = attn + d * 64 + 2 * expert + 2 * d + kvl
    unrouted = dense + 6 * moe + V * d + d
    assert r.params == unrouted + 6 * 64 * expert + V * d
    reached = 64 * (1 - (58 / 64) ** 8)
    assert reached == pytest.approx(34.9, abs=0.05)
    flops, nbytes = r.call(1, 8, 600)
    latent = 7 * 576 * 2
    want = (unrouted + 6 * reached * expert) * 2 + 8 * d * 2 + 600 * latent + 8 * latent
    assert nbytes == pytest.approx(want, rel=1e-12)
    assert nbytes / 1e9 == pytest.approx(4.58, abs=0.01)
    used = 7 * attn + 3 * d * 10944 + 6 * (d * 64 + 8 * expert) + V * d
    assert flops == pytest.approx(8 * (2 * used + 2 * 7 * H * (2 * kvl + 64) * 608), rel=1e-12)


def _v2_root(tmp: Path) -> Path:
    root = smoke.make_root(tmp)
    (root / "bench" / "configs" / "v2.json").write_text(json.dumps(SMOKE))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "v2", "source": "test", "file": "bench/configs/v2.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "v2.single", "config": "v2", "traffic": "single-smoke",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smoke.single" in m.get("workloads", ()):
            m["workloads"].append("v2.single")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_smoke_cell_runs_correct_and_counts_experts(tmp_path):
    from bench import run as R

    root = _v2_root(tmp_path)
    with smoke.jax_config_kept():
        out = R.run(root, "v2.single", 2**31 + 41, 1.5, True, require_tpu=False)
    c = out["check"]
    assert out["correct"] and out["failed"] == 0 and c["requests_compared"] > 0
    assert c["max_gap"] <= c["max_gap_limit"]
    touched = out["metrics"]["experts_touched"]["value"]
    # the tree's live tokens reach at least the 6 experts of one token, at
    # most every one of the 16
    assert 6 <= touched <= 16
    assert out["plant"]["target_on_map"] > 0.5


def test_the_cells_configuration_is_the_catalogs_model():
    """Every number of the published config, as run, bar the cut depth."""
    published = {"first_k_dense_replace": 1, "hidden_size": 2048, "intermediate_size": 10944,
                 "kv_lora_rank": 512, "moe_intermediate_size": 1408, "n_routed_experts": 64,
                 "n_shared_experts": 2, "norm_topk_prob": False, "num_attention_heads": 16,
                 "num_experts_per_tok": 6, "num_key_value_heads": 16, "q_lora_rank": None,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-6,
                 "rope_theta": 10000, "routed_scaling_factor": 1, "v_head_dim": 128,
                 "vocab_size": 102400, "published_num_hidden_layers": 27}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["reduced"] == ["num_hidden_layers"] and CONFIG["num_hidden_layers"] == 7
    assert dataclasses.asdict(LAYOUT.dims(CONFIG))["n_moe"] == 6
