"""A configuration, a traffic mix and a per-layer metric are taken by adding
files and entries only: no code of the harness names them."""

import json

import pytest

from bench import spec
from bench.tests.smoke import SMOKE_CONFIG, make_root


@pytest.fixture()
def root(tmp_path):
    return make_root(tmp_path)


def _add(root, config=None, mix=None, metric=None, cell=None, per_layer=None):
    if config:
        (root / "bench" / "configs" / f"{config[0]}.json").write_text(json.dumps(config[1]))
    if mix:
        (root / "bench" / "traffic" / f"{mix[0]}.json").write_text(json.dumps(mix[1]))
    if metric:
        (root / "bench" / "metrics" / f"{metric[0]}.py").write_text(metric[1])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if cell:
        bench["workloads"].append(cell)
    if per_layer:
        bench["per_layer"].append(per_layer)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_config_mix_and_metric_are_found_by_name(root):
    _add(root,
         config=("tiny-pair", dict(SMOKE_CONFIG, deployment="a new pair")),
         mix=("long-out", {"loop": "closed", "clients": 1, "slots": 1, "prompt_buckets": [8],
                           "prompt_weights": [1.0], "output": {"kind": "fixed", "tokens": 64},
                           "queue": 4, "shape_seed": 3, "check_requests": 1}),
         metric=("rounds_seen", "def read(run):\n    return float(run.spec.rounds) or None\n"),
         cell={"name": "tiny.long", "config": "tiny-pair", "traffic": "long-out", "chips": 1,
               "why": "a cell added as data"},
         per_layer={"name": "rounds_seen", "unit": "rounds", "better": "higher",
                    "source": "program_counter", "layer": "round", "moves": "tok_s",
                    "workloads": ["tiny.long"]})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"]:
        if m["name"] == "tok_s":
            m["workloads"].append("tiny.long")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(root, "tiny.long")
    assert cell.config["deployment"] == "a new pair"
    assert cell.mix["output"]["tokens"] == 64
    assert [m["name"] for m in cell.per_layer] == ["rounds_seen"]
    assert {m["name"] for m in cell.end_to_end} == {"tok_s", "setup_s"}
    read = spec.load_reader(root, "rounds_seen")

    class Run:
        class spec:
            rounds = 7

    assert read(Run) == 7.0


def test_metric_is_read_only_in_the_cells_it_lists(root):
    _add(root, metric=("single_only", "def read(run):\n    return 1.0\n"),
         per_layer={"name": "single_only", "unit": "ms", "better": "lower",
                    "source": "host_clock", "layer": "round", "moves": "tok_s",
                    "workloads": ["smoke.single"]})
    chat = spec.load_cell(root, "smoke.chat")
    single = spec.load_cell(root, "smoke.single")
    assert "single_only" in {m["name"] for m in single.per_layer}
    assert "single_only" not in {m["name"] for m in chat.per_layer}


def test_unknown_names_are_errors(root):
    with pytest.raises(KeyError, match="no workload 'nope'"):
        spec.load_cell(root, "nope")
    with pytest.raises(FileNotFoundError, match="metric 'missing'"):
        spec.load_reader(root, "missing")
    _add(root, cell={"name": "bad.cell", "config": "no-such-config", "traffic": "chat-smoke",
                     "chips": 1, "why": "x"})
    with pytest.raises(FileNotFoundError, match="configs 'no-such-config'"):
        spec.load_cell(root, "bad.cell")


def test_every_metric_of_the_benchmark_has_a_reader():
    from bench.tests.smoke import BENCH

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(BENCH.parent, m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(BENCH.parent, w["name"])
        assert cell.per_layer and any(m["name"] != "setup_s" for m in cell.end_to_end)
