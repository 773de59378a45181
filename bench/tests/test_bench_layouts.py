"""Architectures as files: the harness takes each model's layout and
reference from modules found by the configuration's ``reference`` (and
``draft.reference``) under the benchmark root.

- The Llama layout makes, for the same seed, the weights the harness made
  before layouts were modules, bit for bit, and a smoke serve of them reads
  the same ``plant`` and ``max_gap`` (fingerprints taken on the harness
  before that change).
- A DeepSeek-MoE target (a dense layer, then routed and shared experts: two
  groups of blocks in the program) beside a Llama draft runs to ``correct``
  with its layout and reference present only in the test's root.
- The shared harness files name no architecture.
"""

import hashlib
import json
import re
import shutil
from pathlib import Path

import pytest

from bench.tests import smoke

ARCH = Path(__file__).resolve().parent / "arch_dsmoe"

# sha256 of every leaf of the smoke pair's weights (target, then draft, by
# key path, with dtype and shape), and what a smoke serve of the first four
# requests of ``single-smoke`` reads, per seed
FINGERPRINTS = {
    7: {"weights": "adc537533a12bfdae4f70793c1d249a78f73e33e094c9ef31f3b34e0e0b86e45",
        "max_gap": 0.0, "tokens": 96,
        "plant": {"draft_agreement": 0.65625, "target_on_map": 0.7604166666666666,
                  "chain_tokens_per_round": 2.3902439024390243, "chain_depth": 3}},
    2**31 + 77: {"weights": "69acaa5ae6fc4fcc6d04a4d80413acb14bf3fca79fab8f21ee3bad356a1dbe2e",
                 "max_gap": 0.0, "tokens": 96,
                 "plant": {"draft_agreement": 0.6770833333333334,
                           "target_on_map": 0.7604166666666666,
                           "chain_tokens_per_round": 2.357142857142857, "chain_depth": 3}},
}


def _weights_digest(pair) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for role, tree in (("target", pair.tplain), ("draft", pair.dplain)):
        leaves = jax.tree_util.tree_leaves_with_path(tree)
        for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
            a = np.asarray(leaf)
            h.update(f"{role}{jax.tree_util.keystr(path)}{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def smoke_engine():
    from bench import run as R

    mix = smoke.MIXES["single-smoke"]
    return R.build(smoke.SMOKE_CONFIG, mix), mix


@pytest.mark.parametrize("seed", sorted(FINGERPRINTS))
def test_llama_layout_keeps_weights_plant_and_gap(smoke_engine, seed):
    from bench import run as R
    from bench.traffic.generate import make_items

    eng, mix = smoke_engine
    cfg = smoke.SMOKE_CONFIG
    pair = R.build_pair(eng, cfg, seed)
    want = FINGERPRINTS[seed]
    assert _weights_digest(pair) == want["weights"]
    items = make_items(mix, cfg["vocab_size"], seed, 1.0)[:4]
    _, _, _, results, _ = R.serve(pair, dict(mix, loop="open"), items, 0.0)
    res = R.check(pair, cfg, mix, items, results, seed)
    assert res["tokens"] == want["tokens"]
    assert res["plant"] == want["plant"]
    assert res["max_gap"] == want["max_gap"]


MOE_CONFIG = dict(
    smoke.SMOKE_CONFIG,
    source="https://huggingface.co/deepseek-ai/deepseek-moe-16b-base",
    deployment="test: the program's smoke shapes of DeepSeek-MoE with a DeepSeek-Coder draft",
    hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=2, first_k_dense_replace=1, moe_intermediate_size=96,
    n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=3, norm_topk_prob=True,
    scoring_func="softmax", reference="dsmoe",
    draft=dict(smoke.SMOKE_CONFIG["draft"], reference="llama"),
    # float32, so that no top-k choice of experts is a near-tie that bfloat16
    # rounding could turn (the reference routes in float32)
    program=dict(smoke.SMOKE_CONFIG["program"], target="deepseek-moe-16b", dtype="float32"))


def _moe_root(tmp: Path) -> Path:
    """The smoke root, plus a cell whose target is of an architecture the
    repository's benchmark has no module for."""
    root = smoke.make_root(tmp)
    b = root / "bench"
    shutil.copy(ARCH / "layout.py", b / "layouts" / "dsmoe.py")
    shutil.copy(ARCH / "reference.py", b / "reference" / "dsmoe.py")
    (b / "configs" / "moe.json").write_text(json.dumps(MOE_CONFIG))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "moe", "source": MOE_CONFIG["source"],
                             "file": "bench/configs/moe.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "moe.single", "config": "moe",
                               "traffic": "single-smoke", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "smoke.single" in m.get("workloads", ()):
            m["workloads"].append("moe.single")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_new_architecture_is_new_files_only(tmp_path):
    from bench import run as R

    assert not (smoke.BENCH / "layouts" / "dsmoe.py").exists()
    root = _moe_root(tmp_path)
    with smoke.jax_config_kept():
        out = R.run(root, "moe.single", 2**31 + 41, 1.5, False, require_tpu=False)
    c = out["check"]
    assert out["correct"] and out["failed"] == 0
    assert c["requests_compared"] > 0 and c["max_gap"] <= c["max_gap_limit"]
    # the draft's own reference (llama) read the draft's weights
    assert 0 < out["plant"]["draft_agreement"] <= 1
    assert out["metrics"]["tok_s"]["value"] > 0


def test_a_new_architectures_program_tree_has_two_groups(tmp_path):
    import jax

    from bench import run as R

    root = _moe_root(tmp_path)
    eng = R.build(MOE_CONFIG, smoke.MIXES["single-smoke"], root)
    pair = R.build_pair(eng, MOE_CONFIG, 5, root)
    groups = pair.tparams["groups"]
    assert len(groups) == 2 and "router" in groups[1][0]["mlp"]
    # the program holds the benchmark's arrays themselves, no copies
    assert groups[1][0]["mlp"]["router"].value is pair.tplain["layers"]["moe"]["router"]
    assert jax.tree.structure(pair.dparams) == jax.tree.structure(
        jax.eval_shape(eng.draft.init, jax.random.PRNGKey(0)))


@pytest.mark.parametrize("name", ["run.py", "weights.py", "spec.py"])
def test_the_shared_harness_names_no_architecture(name):
    text = (smoke.BENCH / name).read_text()
    words = r"\b(llama|Llama|moe|MoE|mla|MLA|wq|wk|wv|wo|wg|wu|wd|router|Decoder)\b"
    assert not re.findall(words, text)


@pytest.mark.parametrize("config", sorted(p.stem for p in (smoke.BENCH / "configs").glob("*.json")))
def test_every_configuration_names_its_modules(config):
    from bench import spec

    cfg = spec.load_config(smoke.BENCH.parent, config)
    for arch in {cfg["reference"], cfg["draft"].get("reference", cfg["reference"])}:
        layout = spec.load_layout(smoke.BENCH.parent, arch)
        ref = spec.load_reference(smoke.BENCH.parent, arch)
        assert callable(layout.roofline) and callable(ref.hidden) and callable(ref.head)
    with pytest.raises(FileNotFoundError, match="layouts 'nope'"):
        spec.load_layout(smoke.BENCH.parent, "nope")
