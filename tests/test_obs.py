"""Observability layer (repro.obs): tracer, metrics registry, phase
breakdown, and the instrumented serving stack.

The two load-bearing contracts:
  * disabled tracing is FREE — no-op spans are a cached singleton and the
    per-round hot path allocates nothing (the overhead regression test);
  * enabled tracing explains the round — the phase spans recorded during a
    real continuous-batching run cover >= 95% of every round's wall time,
    so the draft/verify/absorb decomposition is evidence, not guesswork.
"""

import json
import tracemalloc

import numpy as np
import pytest

from repro.core.engine import SpecConfig, SpecEngine
from repro.obs import (
    MetricsRegistry,
    NOOP_SPAN,
    NULL_TRACER,
    Tracer,
    breakdown_report,
    phase_breakdown,
)
from repro.obs.metrics import Histogram, Series
from repro.serving import (
    ContinuousBatchingRuntime,
    Request,
    ShardedServingRuntime,
    VirtualClock,
    merge_summary,
)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


class FakeTime:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def test_span_lifecycle_and_ring_buffer():
    ft = FakeTime()
    tr = Tracer(capacity=4, clock=ft)
    s = tr.begin("a", "t0")
    ft.advance(0.5)
    s.end()
    assert [x.name for x in tr.spans()] == ["a"]
    assert tr.spans()[0].dur == pytest.approx(0.5)
    s.end()  # idempotent: a second end neither re-stamps nor re-records
    assert len(tr.spans()) == 1 and tr.spans()[0].dur == pytest.approx(0.5)

    with tr.span("b", "t0", args={"k": 1}) as sp:
        ft.advance(0.25)
        sp.set("extra", 2)
    assert tr.spans("b")[0].args == {"k": 1, "extra": 2}

    for i in range(6):  # overflow the ring: oldest drop, counted
        with tr.span(f"s{i}"):
            ft.advance(0.1)
    assert len(tr.spans()) == 4
    assert tr.dropped == 4  # a, b, s0, s1 fell out
    assert [x.name for x in tr.spans()] == ["s2", "s3", "s4", "s5"]


def test_chrome_and_jsonl_export():
    ft = FakeTime()
    tr = Tracer(clock=ft)
    with tr.span("round", "replica0"):
        ft.advance(0.002)
    tr.counter("queue_depth", 3)
    tr.counter("occupied", 1, "router")
    doc = tr.to_chrome()
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"round", "occupied", "queue_depth", "thread_name"} <= names
    x = next(e for e in evs if e["ph"] == "X")
    assert x["ts"] == pytest.approx(0.0) and x["dur"] == pytest.approx(2000.0)
    meta = {e["args"]["name"]: e["tid"] for e in evs if e["ph"] == "M"}
    assert meta.keys() == {"replica0", "router", "counters"}
    assert x["tid"] == meta["replica0"]
    c = next(e for e in evs if e["ph"] == "C")
    assert c["args"] == {"queue_depth": 3}
    json.dumps(doc)  # serializable as-is

    line = tr.to_jsonl().strip()
    rec = json.loads(line)
    assert rec == {"name": "round", "track": "replica0", "t0": 0.0,
                   "t1": pytest.approx(0.002), "dur": pytest.approx(0.002)}


def test_write_picks_format_from_extension(tmp_path):
    tr = Tracer()
    with tr.span("x"):
        pass
    p1 = tr.write(str(tmp_path / "trace.json"))
    assert "traceEvents" in json.load(open(p1))
    p2 = tr.write(str(tmp_path / "trace.jsonl"))
    assert json.loads(open(p2).read().splitlines()[0])["name"] == "x"


# ---------------------------------------------------------------------------
# the overhead regression: disabled tracing is free
# ---------------------------------------------------------------------------


def test_disabled_tracer_noop_singleton_zero_allocation():
    """The disabled per-round path returns ONE cached object and allocates
    nothing — adding instrument points must never tax an untraced server."""
    tr = Tracer(enabled=False)
    assert tr.begin("round") is NOOP_SPAN
    assert tr.span("absorb", "replica0") is NOOP_SPAN
    assert NULL_TRACER.begin("x") is NOOP_SPAN

    def per_round():
        s = tr.begin("round", "replica0")
        with tr.span("verify_dispatch", "replica0"):
            pass
        with tr.span("absorb", "replica0"):
            pass
        tr.counter("queue_depth", 1)
        tr.counter("occupied", 1, "router")
        s.set("k", 1)
        s.end()

    import repro.obs.trace as trace_mod

    obs_dir = trace_mod.__file__.rsplit("/", 1)[0]
    tracemalloc.start()
    try:
        for _ in range(100):  # absorb one-time warmup (caches, interning)
            per_round()
        snap1 = tracemalloc.take_snapshot()
        for _ in range(1000):
            per_round()
        snap2 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = [s for s in snap2.compare_to(snap1, "lineno")
             if s.size_diff > 0 and s.traceback[0].filename.startswith(obs_dir)]
    leaked = sum(s.size_diff for s in grown)
    # CPython caches one "zombie frame" per function (~113 B, constant); a
    # real per-round allocation would be >= 16 KiB over 1000 rounds
    assert leaked < 2048, f"disabled tracer allocated over 1000 rounds: {grown}"
    assert len(tr.spans()) == 0 and tr.dropped == 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_handles_are_get_or_create():
    m = MetricsRegistry()
    c = m.counter("rounds", replica="0")
    assert m.counter("rounds", replica="0") is c
    assert m.counter("rounds", replica="1") is not c
    c.inc()
    c.inc(2)
    assert c.value == 3
    g = m.gauge("occ")
    g.set(0.5)
    snap = m.snapshot()
    assert {"name": "rounds", "labels": {"replica": "0"}, "value": 3.0} in snap["counters"]
    assert snap["gauges"] == [{"name": "occ", "labels": {}, "value": 0.5}]


def test_histogram_buckets_sum_count():
    h = Histogram(buckets=(0, 1, 2, 4))
    for x in (0, 1, 1, 3, 99):
        h.observe(x)
    assert h.counts == [1, 2, 0, 1, 1]  # le=0,1,2,4,+Inf (non-cumulative)
    assert h.count == 5 and h.sum == 104.0
    assert h.mean == pytest.approx(20.8)
    with pytest.raises(ValueError):
        Histogram(buckets=(2, 1))
    with pytest.raises(ValueError):
        Histogram(buckets=())


def test_series_is_bounded():
    s = Series(maxlen=3)
    for i in range(5):
        s.append(float(i), i * 10)
    assert s.values() == [20, 30, 40] and s.dropped == 2 and s.last == 40


def test_prometheus_text_format():
    m = MetricsRegistry()
    m.counter("serving_rounds_total", replica="0").inc(7)
    h = m.histogram("serving_accept_depth", buckets=(0, 1, 2), replica="0")
    for x in (0, 1, 1, 5):
        h.observe(x)
    m.series("serving_queue_depth").append(0.0, 4)
    text = m.to_prometheus()
    assert '# TYPE serving_rounds_total counter' in text
    assert 'serving_rounds_total{replica="0"} 7' in text
    # histogram buckets are CUMULATIVE with an +Inf bucket, plus _sum/_count
    assert 'serving_accept_depth_bucket{le="0",replica="0"} 1' in text
    assert 'serving_accept_depth_bucket{le="1",replica="0"} 3' in text
    assert 'serving_accept_depth_bucket{le="+Inf",replica="0"} 4' in text
    assert 'serving_accept_depth_sum{replica="0"} 7' in text
    assert 'serving_accept_depth_count{replica="0"} 4' in text
    assert 'serving_queue_depth 4' in text


def test_metrics_write_json_and_prom(tmp_path):
    m = MetricsRegistry()
    m.counter("c").inc()
    p = m.write(str(tmp_path / "m.json"), extra={"phase_breakdown": {"x": 1}})
    doc = json.load(open(p))
    assert doc["phase_breakdown"] == {"x": 1} and doc["counters"][0]["name"] == "c"
    p = m.write(str(tmp_path / "m.prom"))
    assert "# TYPE c counter" in open(p).read()


# ---------------------------------------------------------------------------
# phase breakdown
# ---------------------------------------------------------------------------


def _round(tr, ft, track, phases, gap=0.0):
    r = tr.begin("round", track)
    for name, dt in phases:
        with tr.span(name, track):
            ft.advance(dt)
    ft.advance(gap)
    r.end()


def test_phase_breakdown_synthetic():
    ft = FakeTime()
    tr = Tracer(clock=ft)
    phases = [("verify_dispatch", 0.2), ("draft_expand", 0.3),
              ("sync_emitted", 0.1), ("reroot_grow", 0.25), ("absorb", 0.1)]
    _round(tr, ft, "replica0", phases, gap=0.05)  # covered 0.95 of 1.0
    _round(tr, ft, "replica0", phases, gap=0.0)   # covered 1.0 of 0.95

    bd = phase_breakdown(tr)
    assert bd["n_rounds"] == 2
    assert bd["round_total_s"] == pytest.approx(1.95)
    assert bd["phase_s"]["draft_expand"] == pytest.approx(0.6)
    assert bd["draft_s"] == pytest.approx(1.1)    # expand + reroot_grow
    assert bd["verify_s"] == pytest.approx(0.6)   # dispatch + sync
    assert bd["absorb_s"] == pytest.approx(0.2)
    assert bd["draft_frac"] == pytest.approx(1.1 / 1.95)
    assert bd["coverage_min"] == pytest.approx(0.95)
    assert bd["coverage_mean"] == pytest.approx((0.95 + 1.0) / 2)
    rep = breakdown_report(bd)
    assert "draft" in rep and "2 rounds" in rep


def test_phase_breakdown_ignores_nested_and_foreign_spans():
    """Only the five top-level phases count: a ``retire`` nested inside
    ``absorb`` (or admit spans between rounds) must not double-count
    coverage, and another track's phases never leak across."""
    ft = FakeTime()
    tr = Tracer(clock=ft)
    with tr.span("admit_prefill", "replica0"):
        ft.advance(0.3)
    r = tr.begin("round", "replica0")
    with tr.span("verify_dispatch", "replica0"):
        ft.advance(0.5)
    with tr.span("absorb", "replica0"):
        with tr.span("retire", "replica0"):
            ft.advance(0.2)
        ft.advance(0.3)
    r.end()
    # a concurrent round on another track with its own phases
    _round(tr, ft, "replica1", [("draft_expand", 0.4)])
    bd = phase_breakdown(tr)
    assert bd["n_rounds"] == 2
    assert bd["coverage_min"] <= 1.0 and bd["coverage_mean"] <= 1.0
    assert bd["phase_s"]["verify_dispatch"] == pytest.approx(0.5)
    assert bd["phase_s"]["absorb"] == pytest.approx(0.5)
    assert bd["phase_s"]["draft_expand"] == pytest.approx(0.4)


def test_phase_breakdown_empty_is_nan_marked():
    """Zero rounds must read as 'unknown' (nan), never as an instantaneous
    round with perfect-zero coverage — a dead tracer that reported 0.0s
    rounds would slide straight past the CI coverage gate."""
    bd = phase_breakdown(Tracer())
    assert bd["n_rounds"] == 0 and bd["round_total_s"] == 0.0
    assert np.isnan(bd["mean_round_s"])
    assert np.isnan(bd["coverage_mean"]) and np.isnan(bd["coverage_min"])
    assert all(np.isnan(v) for v in bd["phase_frac"].values())
    for group in ("draft", "verify", "absorb"):
        assert bd[f"{group}_s"] == 0.0 and np.isnan(bd[f"{group}_frac"])
    assert breakdown_report(bd) == "phase breakdown: no rounds traced"


def test_merge_summary_no_replicas_is_nan_marked():
    """merge_summary([]) — a fleet that never started — must not divide by
    zero and must nan-mark the rate fields rather than report 0 tok/s."""
    s = merge_summary([])
    assert s["n_replicas"] == 0 and s["n_finished"] == 0
    assert np.isnan(s["throughput_tok_s"])
    assert np.isnan(s["ttft_p50_s"]) and np.isnan(s["ttft_p99_s"])
    assert s["mean_occupancy"] == 0.0 and s["mean_acceptance"] == 0.0


# ---------------------------------------------------------------------------
# the instrumented serving stack, end to end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def obs_engine(dense_pair):
    T, D, tp, dp = dense_pair
    cfg = SpecConfig(bs=8, w=4, c=2, d=2, n_cap=64, mode="parallel", max_new=24)
    return SpecEngine(T, D, cfg, S_max_t=256, S_max_d=256), tp, dp


def _prompt(k, P=8):
    return ((np.arange(1, P + 1) * k + 3) % 128).astype(np.int32)


def test_traced_continuous_run_covers_rounds(obs_engine):
    """The acceptance contract: a traced serving run produces round spans
    whose draft/verify/absorb children explain >= 95% of each round, and a
    metrics snapshot with the accept-depth histogram, per-replica round
    counters, queue-depth samples, and TTFT observations."""
    eng, tp, dp = obs_engine
    tracer, metrics = Tracer(), MetricsRegistry()
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=2, clock=VirtualClock(),
                                   tracer=tracer, metrics=metrics)
    reqs = [Request(rid=i, prompt=_prompt(i + 1, P=8 + 4 * (i % 2)),
                    arrival_s=0.7 * i, max_new=12) for i in range(4)]
    assert rt.submit_trace(reqs) == 4
    results = rt.run()
    assert sorted(results) == [0, 1, 2, 3]

    # --- spans: every engine round traced, phases cover the round wall time
    rounds = tracer.spans("round")
    assert len(rounds) == rt.stats.rounds
    bd = phase_breakdown(tracer)
    assert bd["n_rounds"] == rt.stats.rounds
    assert bd["coverage_min"] >= 0.95, breakdown_report(bd)
    for phase in ("verify_dispatch", "draft_expand", "sync_emitted",
                  "reroot_grow", "absorb"):
        assert bd["phase_s"][phase] > 0.0, f"phase {phase} never recorded"
    # admission + routing instrumented too
    assert len(tracer.spans("admit_prefill")) == 4
    assert len(tracer.spans("retire")) == 4
    routes = [s for s in tracer.spans("route") if s.args]
    assert {s.args["rid"] for s in routes} == {0, 1, 2, 3}
    assert len(tracer.counters("queue_depth")) == rt.stats.rounds

    # --- metrics: the snapshot the adaptive-depth work will read
    assert metrics.counter("serving_rounds_total", replica="0").value == rt.stats.rounds
    assert metrics.counter("serving_admitted_total", replica="0").value == 4
    assert metrics.counter("serving_finished_total", replica="0").value == 4
    total_tokens = sum(len(v) for v in results.values())
    assert metrics.counter("serving_tokens_total", replica="0").value == total_tokens
    h = metrics.histogram("serving_accept_depth", replica="0")
    assert h.count == sum(r.n_rounds for r in rt.stats.records.values())
    assert h.sum == sum(r.n_accepted for r in rt.stats.records.values())
    ttft = metrics.histogram("serving_ttft_seconds", replica="0")
    assert ttft.count == 4
    q = metrics.series("serving_queue_depth")
    assert len(q.samples) == rt.stats.rounds
    occ = metrics.series("serving_occupancy", replica="0")
    assert [int(v) for v in occ.values()] == rt.stats.occupancy_samples


def test_untraced_run_is_unchanged(obs_engine):
    """Default construction (no tracer) still serves identically and keeps
    metrics, with zero spans recorded anywhere."""
    eng, tp, dp = obs_engine
    rt = ContinuousBatchingRuntime(eng, tp, dp, n_slots=1, clock=VirtualClock())
    rt.submit(Request(rid=0, prompt=_prompt(5), max_new=8))
    results = rt.run()
    solo, _ = eng.generate(tp, dp, _prompt(5).reshape(1, -1), max_new=8)
    assert results[0] == solo[0]
    assert rt.tracer is NULL_TRACER and len(NULL_TRACER.spans()) == 0
    assert rt.metrics.counter("serving_finished_total", replica="0").value == 1


def test_sharded_metrics_per_replica_labels(obs_engine):
    """Two replicas: spans land on separate tracks and metrics carry the
    owning replica's label, so the fleet view decomposes."""
    eng, tp, dp = obs_engine
    tracer, metrics = Tracer(), MetricsRegistry()
    rt = ShardedServingRuntime([eng, eng], tp, dp, n_slots=1,
                               clock=VirtualClock(), tracer=tracer,
                               metrics=metrics)
    reqs = [Request(rid=i, prompt=_prompt(3 + i), arrival_s=0.0, max_new=6)
            for i in range(2)]
    rt.submit_trace(reqs)
    rt.run()
    tracks = {s.track for s in tracer.spans("round")}
    assert tracks == {"replica0", "replica1"}
    for i in (0, 1):
        assert metrics.counter("serving_admitted_total", replica=str(i)).value == 1
        assert metrics.counter("serving_rounds_total",
                               replica=str(i)).value == rt.steppers[i].stats.rounds
    snap = metrics.snapshot()
    fam = [c for c in snap["counters"] if c["name"] == "serving_rounds_total"]
    assert {c["labels"]["replica"] for c in fam} == {"0", "1"}
