"""The four-chip configuration ``dscoder33b-l20-tp2_tp2`` through its layout
module on four virtual CPU devices (the look for a chip skipped): its
configuration file as committed, with the model numbers swapped for the
program's smoke shapes of the same pair, runs to ``correct``; the layout
puts each model's weights on its own pair of chips, split over the
tensor-parallel axis; and the layout's roofline counts the committed
configuration at 11.07 GB of target weights per chip.

Four devices need ``XLA_FLAGS`` before JAX starts, so the run is a child
process."""

import json
import os
import subprocess
import sys
import textwrap

from bench import spec
from bench.tests.smoke import BENCH

CONFIG = "dscoder33b-l20-tp2_tp2"

SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [sys.argv[2], sys.argv[2] + "/src"]
    from bench import run as R, spec
    from bench.tests import smoke

    real = spec.load_config(Path(sys.argv[2]), sys.argv[3])
    sm = smoke.SMOKE_CONFIG
    model_keys = [k for k in sm if k not in ("program", "draft", "source", "deployment",
                                             "chips", "reduced", "assumed", "reference",
                                             "check")]
    config = dict(real, **{k: sm[k] for k in model_keys},
                  draft=dict(real["draft"], **sm["draft"]),
                  program=dict(real["program"], smoke=True))
    config.pop("runs_as")  # the smoke shapes are as the program runs them
    root = smoke.make_root(Path(sys.argv[1]))
    (root / "bench" / "configs" / "tp2.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "tp2.single", "config": "tp2", "traffic": "single-smoke",
                           "chips": 4, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tp2.single"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    run = R.run(root, "tp2.single", 2**31 + 91, 1.5, False, require_tpu=False)
    eng = R.build(config, smoke.MIXES["single-smoke"], root)
    pair = R.build_pair(eng, config, 2**31 + 91, root)

    def where(x):
        return {"devices": sorted(d.id for d in x.sharding.device_set),
                "shard": list(x.addressable_shards[0].data.shape), "shape": list(x.shape)}

    print(json.dumps({
        "run": {"correct": run["correct"], "check": run["check"], "count": run["device"]["count"],
                "n": [pair.n_target, pair.n_draft]},
        "target": {k: where(pair.tplain["layers"][k]) for k in ("wq", "wk", "wo", "wg", "wd")},
        "draft": {k: where(pair.dplain["layers"][k]) for k in ("wq", "wg")},
        "heads": [config["num_attention_heads"], config["draft"]["num_attention_heads"]]}))
""")


def test_tp2_configuration_runs_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path), str(BENCH.parent),
                           CONFIG], env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run = out["run"]
    assert run["count"] == 4 and run["n"] == [2, 2]
    assert run["correct"] and run["check"]["requests_compared"] > 0
    hq, hq_draft = out["heads"]
    # the target's weights on chips 0-1, the draft's on 2-3, each split in two
    # over heads or the MLP's width
    for k, w in out["target"].items():
        assert w["devices"] == [0, 1], k
    assert out["target"]["wq"]["shard"][2] == hq // 2
    assert out["target"]["wg"]["shard"][2] * 2 == out["target"]["wg"]["shape"][2]
    for k, w in out["draft"].items():
        assert w["devices"] == [2, 3], k
    assert out["draft"]["wq"]["shard"][2] == hq_draft // 2


def test_tp2_layout_roofline_counts_the_committed_configuration():
    config = spec.load_config(BENCH.parent, CONFIG)
    layout = spec.load_layout(BENCH.parent, config["reference"])
    target = layout.roofline(config)
    # (20 x 1,060,663,296 + 924,858,368) / 2, as test_bench_roofline counts by hand
    assert target.param_bytes(tp=config["program"]["n_target"]) == 11_069_062_144
    draft = layout.roofline(config["draft"])
    assert draft.param_bytes(tp=config["program"]["n_draft"]) == 2_692_943_872 / 2
