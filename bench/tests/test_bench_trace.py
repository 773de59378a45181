"""The reduction from a profiler trace to device metrics: busy union, idle
share, per-program time and idle gaps named by the host span around them."""

import json

import jax
import jax.numpy as jnp
import pytest

from bench import xtrace
from bench.obs import ProfilerTracer, annotate
from bench.tests.smoke import BENCH

RECORDED = BENCH / "tests" / "data" / "tpu_trace.json"


def _record():
    return {
        "t0": 0, "t1": 400,
        "devices": {"/device:TPU:0": {"modules": [
            ["jit_verify", 100, 50], ["jit_expand", 160, 30], ["jit_expand", 180, 40],
            ["jit_compact", 300, 20]]}},
        "host": [["bench.window", 0, 400], ["round", 90, 300], ["verify_dispatch", 95, 100],
                 ["sync_emitted", 230, 60], ["absorb", 330, 30]],
    }


def test_busy_union_and_idle_share_by_hand():
    red = xtrace.reduce(_record())
    # [100,150) + [160,220) (two overlapping expands) + [300,320)
    assert red["busy_s"] == pytest.approx(130e-9)
    assert red["window_s"] == pytest.approx(400e-9)
    assert red["idle_share"] == pytest.approx(1 - 130 / 400)


def test_time_per_program_by_hand():
    red = xtrace.reduce(_record())
    progs = red["programs"]
    assert progs["jit_expand"] == {"s": pytest.approx(70e-9), "calls": 2}
    assert progs["jit_verify"]["calls"] == 1 and progs["jit_compact"]["calls"] == 1
    assert red["device_ops"][0][0] == "jit_expand"


def test_gaps_go_to_the_innermost_host_span():
    gaps = dict(xtrace.reduce(_record())["idle_gaps"])
    # (0,100): only the window covers it; (150,160): verify_dispatch;
    # (220,300): sync_emitted; (320,400): absorb ends at 360, round covers
    assert gaps == {"none": pytest.approx(100e-9), "sync_emitted": pytest.approx(80e-9),
                    "round": pytest.approx(80e-9), "verify_dispatch": pytest.approx(10e-9)}
    assert sum(gaps.values()) == pytest.approx(400e-9 - 130e-9)


def test_window_clips_events():
    rec = _record()
    rec["devices"]["/device:TPU:0"]["modules"].append(["jit_verify", 390, 50])
    red = xtrace.reduce(rec)
    assert red["busy_s"] == pytest.approx(140e-9)


def test_a_trace_recorded_with_the_benchmark(tmp_path):
    """Record a short trace through the benchmark's annotations and the
    program-facing tracer, and reduce it."""
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    tracer = ProfilerTracer()
    with annotate("window"):
        for _ in range(3):
            with tracer.span("round"):
                f(x).block_until_ready()
            span = tracer.begin("sync_emitted")
            span.end()
    jax.profiler.stop_trace()
    rec = xtrace.extract(xtrace.find_xplane(str(tmp_path)))
    names = {h[0] for h in rec["host"]}
    assert {"bench.window", "round", "sync_emitted"} <= names
    assert rec["t0"] is not None and rec["t1"] > rec["t0"]
    red = xtrace.reduce(rec)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["idle_share"] < 1
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6, abs=1e-9)
    assert xtrace.mean_busy(rec) == pytest.approx(red["busy_s"])


# every reader kept under bench/metrics/, by the smoke cell whose run has
# something for it to read (the chat readers' cell is not in BENCHMARK.json)
READERS = {"smoke.single": ["tokens_per_round", "commit_rate", "verify_ms", "draft_ms",
                            "idle_share"],
           "smoke.chat": ["queue_wait_p90_ms", "round_ms.chat"]}


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    from bench.tests.smoke import make_root

    root = make_root(tmp_path_factory.mktemp("bench-trace"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": n, "unit": "x", "better": "lower", "source": "host_clock",
                           "layer": "any", "moves": "setup_s", "workloads": [cell]}
                          for cell, names in READERS.items() for n in names]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.mark.parametrize("cell", sorted(READERS))
def test_traced_run_reads_every_metric_that_needs_no_peaks(smoke_root, cell):
    """A whole ``--trace 1`` run at smoke shapes on the CPU (the look for a
    chip skipped): the result line holds each reader's metric, and the
    device's busy and window seconds.  The rooflines and ``round_mfu`` need
    the chip's peaks and are left out on the CPU."""
    from bench import run as R
    from bench.tests.smoke import jax_config_kept

    with jax_config_kept():
        out = R.run(smoke_root, cell, 2**31 + 11, 1.5, True, require_tpu=False)
    assert set(READERS[cell]) <= set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["correct"]


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded chip trace")
def test_a_chip_trace():
    """A slice of a trace recorded on a v5e by ``bench/run.py --trace 1``."""
    rec = json.loads(RECORDED.read_text())
    red = xtrace.reduce(rec)
    assert red["device"].startswith("/device:TPU:")
    assert 0 < red["busy_s"] <= red["window_s"]
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert {"jit_verify", "jit_expand"} <= set(red["programs"])
