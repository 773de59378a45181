"""The readers of the program's round counters: host time per round and in
the sync (``host_ms``, ``sync_wait_ms``, ``host_max_ms``), and the share of
the program's verify dispatches that the device trace holds
(``trace_coverage``)."""

import types

import pytest

from bench import spec, xtrace
from bench.tests.smoke import BENCH

COUNTERS = ("host_ms", "sync_wait_ms", "host_max_ms", "trace_coverage")


def _reader(name):
    return spec.load_reader(BENCH.parent, name)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A whole ``--trace 1`` smoke run on the CPU, and the run data its
    readers were handed."""
    from bench import run as R
    from bench.tests.smoke import jax_config_kept, make_root

    root = make_root(tmp_path_factory.mktemp("bench-counters"))
    seen = {}
    load = spec.load_reader

    def load_and_keep(root, name):
        read = load(root, name)

        def keep(run):
            seen["run"] = run
            return read(run)
        return keep

    spec.load_reader = load_and_keep
    try:
        with jax_config_kept():
            out = R.run(root, "smoke.single", 2**31 + 29, 1.5, True, require_tpu=False)
    finally:
        spec.load_reader = load
    return out, seen["run"]


def test_a_traced_run_reads_the_host_counters(traced):
    out, run = traced
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"host_ms", "sync_wait_ms", "host_max_ms"} <= set(m) and out["correct"]
    # the two parts add up to the program's round time, which lies inside
    # the run's wall time
    per_round = 1e3 * run.server.round_s / run.server.rounds
    assert m["host_ms"] + m["sync_wait_ms"] == pytest.approx(per_round)
    assert run.server.round_s <= run.run_s
    assert 0 < m["host_ms"] <= m["host_max_ms"]
    # the CPU backend's trace has one event per operation: no coverage
    assert out["device"]["platform"] == "cpu" and "trace_coverage" not in m


def test_the_counted_programs_are_named_as_the_trace_names_them(traced):
    _, run = traced
    counted, traced_progs = run.spec.dispatches, run.trace["programs"]
    assert counted["jit_verify"] == run.spec.rounds == run.server.rounds
    assert set(counted) <= set(traced_progs)
    # every program of the round has a name of its own
    assert "jit__lambda" not in traced_progs
    # at least one operation event per verify dispatch in a whole-run trace
    assert traced_progs["jit_verify"]["calls"] >= counted["jit_verify"]


def _device_run(n_traced, n_dispatched, device="/device:TPU:0"):
    modules = [["jit_verify", 100 * i, 50] for i in range(n_traced)]
    rec = {"t0": 0, "t1": 100 * max(n_traced, 1), "devices": {device: {"modules": modules}},
           "host": [["bench.window", 0, 100 * max(n_traced, 1)]]}
    return types.SimpleNamespace(trace=xtrace.reduce(rec),
                                 spec=types.SimpleNamespace(dispatches={"jit_verify": n_dispatched}))


@pytest.mark.parametrize("n_traced, n_dispatched, share", [(30, 30, 100.0), (20, 30, 66.67)])
def test_trace_coverage_by_hand(n_traced, n_dispatched, share):
    assert _reader("trace_coverage")(_device_run(n_traced, n_dispatched)) == pytest.approx(
        share, abs=0.01)


def test_trace_coverage_reads_nothing_on_the_cpu_backend():
    assert _reader("trace_coverage")(_device_run(30, 30, device="/host:CPU")) is None


def test_a_program_without_the_counters_reads_nothing():
    """On a program that keeps no round counters each reader returns None
    and does not raise."""
    run = _device_run(30, 30)
    run.spec = types.SimpleNamespace(rounds=30)
    run.server = types.SimpleNamespace(rounds=30, records={})
    for name in COUNTERS:
        assert _reader(name)(run) is None, name
