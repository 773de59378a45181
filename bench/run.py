"""Run one benchmark cell once on the accelerator.

    python bench/run.py --workload coder33b-l6.single --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix, found
by name under ``bench/`` (``spec.py``).  The run:

  1. fails, printing no result, unless JAX finds a TPU with the chips the
     cell asks for;
  2. builds the target/draft pair through ``serving_configs`` and
     ``build_engine`` with the configuration's settings, and replaces the
     engine's weights with the benchmark's own, made from ``--seed`` with a
     planted next-token map (``weights.py``);
  3. warms every program the cell's traffic uses (each prompt bucket, the
     slot count, retire and re-admit, the lookahead's commit and roll-back);
     all of this is ``setup_s``;
  4. serves the mix through ``ContinuousBatchingRuntime.run`` for
     ``--seconds`` seconds on a wall clock, counting compilations inside the
     window, then lets the requests due in the window finish;
  5. compares a seeded sample of the finished requests with the plain
     float32 reference (``reference/``): the widest gap by which a served
     token's logit lies below the reference's best;
  6. prints the end-to-end metrics (``--trace 0``) or, under the profiler, the
     per-layer metrics read by ``bench/metrics/<name>.py`` (``--trace 1``).

Nothing here depends on the architecture.  Each model's architecture is
named by the configuration (``reference``, and ``draft.reference`` where the
draft's differs) and supplied by two modules found by that name under the
benchmark root: ``layouts/<name>.py`` (sizes, the check that the program runs
the configuration's model, the seeded layer weights, their mapping onto the
program's parameter tree and shardings, the roofline counts) and
``reference/<name>.py`` (the plain float32 forward).  So a model of another
architecture is new files only.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error.  JAX's persistent
compilation cache lives in ``JAX_COMPILATION_CACHE_DIR`` when set, else in
``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def check_device(chips: int, require_tpu: bool = True):
    # before the backend starts: the TPU runtime would log to a fixed
    # directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found platform {d0.platform!r} "
                       f"({d0.device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found {len(devs)} "
                       f"{d0.device_kind} device(s)")
    return devs


def enable_cache(root: Path) -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    # every program, however fast it compiles, so a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# the pair
# ---------------------------------------------------------------------------

def as_run(model: dict, config: dict) -> dict:
    """A model's published numbers with the configuration's ``runs_as``
    departures of the program (the same for the target and the draft)."""
    return {**model, **config.get("runs_as", {})}


@dataclasses.dataclass(frozen=True)
class Model:
    """One model of the pair: its published numbers as run, and the layout
    and reference modules of its architecture."""

    role: str
    hf: dict
    arch: str
    layout: object  # bench/layouts/<arch>.py
    ref: object  # bench/reference/<arch>.py


def models(config: dict, root: Path = ROOT) -> tuple[Model, Model]:
    """The target and the draft.  The configuration's ``reference`` names the
    architecture of both; ``draft.reference``, where given, the draft's."""
    from bench import spec

    out = []
    for role, hf, arch in (("target", config, config["reference"]),
                           ("draft", config["draft"],
                            config["draft"].get("reference", config["reference"]))):
        out.append(Model(role, as_run(hf, config), arch, spec.load_layout(root, arch),
                         spec.load_reference(root, arch)))
    return tuple(out)


@dataclasses.dataclass
class Pair:
    engine: object
    tparams: object
    dparams: object
    tplain: dict  # the target's weights, in its reference's layout
    n_target: int
    n_draft: int
    dplain: dict | None = None  # the draft's weights, in its reference's layout
    pi: object = None  # the target's planted next-token map, i64[V]


def build(config: dict, mix: dict, root: Path = ROOT):
    """The engine ``build_engine`` gives for the configuration (its own
    seeded weights are dropped: the benchmark makes its own)."""
    from bench.traffic.generate import cache_rows, max_output
    from repro.launch.serve import build_engine, serving_configs

    prog = config["program"]
    cfgT, cfgD = serving_configs(prog["target"], prog["draft"], smoke=prog.get("smoke", False),
                                 target_layers=config["num_hidden_layers"], dtype=prog["dtype"])
    for m, mc in zip(models(config, root), (cfgT, cfgD)):
        m.layout.same_model(m.role, m.hf, mc)
    eng, tp0, dp0, _ = build_engine(
        cfgT, cfgD, mode="parallel", bs=prog["bs"], w=prog["w"], c=prog["c"], d=prog["d"],
        max_new=max_output(mix), S_max=cache_rows(mix, prog["bs"]),
        n_target=prog["n_target"], n_draft=prog["n_draft"], async_rounds=prog["async_rounds"])
    del tp0, dp0
    gc.collect()
    return eng


def build_pair(eng, config: dict, seed: int, root: Path = ROOT) -> Pair:
    """The benchmark's weights for ``seed`` on the engine's meshes: made in
    each model's reference layout (``weights.make_fn``) with the program's
    shardings, and handed to the program as its own tree without a copy."""
    import jax
    import numpy as np

    from bench import weights as W
    from repro.sharding import sharding_for_tree

    prog, a = config["program"], config["assumed"]
    inv_t, inv_d, keep = W.plant_maps(config["vocab_size"], seed,
                                      disagree=1.0 - a["draft_agreement"], free=a["free_share"])
    out = []
    for m, model, mesh, inv, kp, stream in zip(
            models(config, root), (eng.target, eng.draft), (eng.mesh_target, eng.mesh_draft),
            (inv_t, inv_d), (keep, None), (10, 11)):
        like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        dims = m.layout.dims(m.hf)
        fn = W.make_fn(m.layout, dims, prog["dtype"], a["logit_scale"],
                       out_shardings=m.layout.plain_shardings(sharding_for_tree(mesh, like)))
        kp = kp if kp is not None else [1.0] * dims.vocab
        plain = fn(W.key_for(seed, stream), jax.numpy.asarray(inv), jax.numpy.asarray(kp))
        tree = m.layout.program_tree(plain, like)
        if jax.tree.structure(tree) != jax.tree.structure(like):
            raise ValueError(f"{m.role}: bench/layouts/{m.arch}.py maps the benchmark's "
                             f"weights onto another tree than the program's")
        out.append((plain, tree))
    (tplain, tparams), (dplain, dparams) = out
    jax.block_until_ready((tparams, dparams))
    return Pair(eng, tparams, dparams, tplain, prog["n_target"], prog["n_draft"],
                dplain=dplain, pi=np.argsort(inv_t))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Req:
    rid: int
    due_s: float
    plen: int
    max_new: int
    deliveries: list = dataclasses.field(default_factory=list)  # (t, n_tokens)

    @property
    def n_tokens(self) -> int:
        return sum(n for _, n in self.deliveries)


def _queue_cls():
    from repro.serving import RequestQueue

    class StampedQueue(RequestQueue):
        """The program's queue, noting when each request reached it."""

        def __init__(self, clock, **kw):
            super().__init__(**kw)
            self.clock, self.entered = clock, {}

        def submit(self, req):
            self.entered[req.rid] = self.clock.now()
            return super().submit(req)

        def reject(self, req):
            self.entered[req.rid] = self.clock.now()
            return super().reject(req)

    return StampedQueue


def serve(pair: Pair, mix: dict, items, seconds: float, *, tracer=None, on_open=None):
    """Serve ``items`` through a fresh ContinuousBatchingRuntime on the
    engine.  Open loop: each item arrives when due.  Closed loop: ``clients``
    callers send their next item when the last finished, until ``seconds``.
    Returns (runtime, {rid: Req}, clock, results, queue)."""
    from bench.obs import BenchClock
    from repro.serving import ContinuousBatchingRuntime, Request

    clock = BenchClock()
    reqs: dict[int, Req] = {}
    pending = list(items)
    closed = mix["loop"] == "closed"
    queue = _queue_cls()(clock, cap=int(mix.get("queue_cap", 64)))
    state = {"rt": None}

    def submit(it, due):
        reqs[it.rid] = Req(it.rid, due, int(it.prompt.size), it.max_new)
        state["rt"].submit(Request(rid=it.rid, prompt=it.prompt, arrival_s=due,
                                   max_new=it.max_new, eos_id=-1))

    def stream(rid, new, done):
        t = clock.now()
        r = reqs[rid]
        if new:
            r.deliveries.append((t, len(new)))
        if done and closed and pending and t < seconds:
            submit(pending.pop(0), t)

    rt = ContinuousBatchingRuntime(pair.engine, pair.tparams, pair.dparams,
                                   n_slots=int(mix["slots"]), queue=queue, clock=clock,
                                   stream=stream, tracer=tracer)
    state["rt"] = rt
    first = [pending.pop(0) for _ in range(int(mix.get("clients", 1)))] if closed else pending
    for it in first:
        submit(it, 0.0 if closed else it.due_s)
    if not closed:
        pending = []
    if on_open is not None:
        on_open()
    results = rt.run()
    return rt, reqs, clock, results, queue


def warm_up(pair: Pair, mix: dict, vocab: int) -> dict:
    """Serve a few requests of every prompt bucket through the cell's slot
    count: every program the window runs compiles (or loads) here."""
    import numpy as np

    from bench.traffic.generate import Item, rng

    g = rng(0, 99)
    buckets = list(mix["prompt_buckets"])
    n = max(2 * int(mix["slots"]), len(buckets)) + 1
    items = [Item(i, 0.0, g.integers(0, vocab, buckets[i % len(buckets)], dtype=np.int32), 24)
             for i in range(n)]
    wmix = dict(mix, loop="open", queue_cap=max(64, n))
    rt, reqs, clock, results, _ = serve(pair, wmix, items, 0.0)
    st = rt.stepper.spec_stats
    recs = rt.stats.records.values()
    rounds = sum(r.n_rounds for r in recs)
    return {"requests": len(results), "tokens_per_round":
            sum(r.n_tokens for r in recs) / max(rounds, 1),
            "accepted_per_round": sum(r.n_accepted for r in recs) / max(rounds, 1),
            "commit_rate": st.spec_commits / max(st.spec_rounds, 1)}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def quantile(xs, q: float) -> float | None:
    """The q-quantile (0 < q < 1) by linear interpolation between order
    statistics (numpy's default)."""
    xs = sorted(xs)
    if not xs:
        return None
    pos = (len(xs) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def end_to_end(reqs: dict, seconds: float) -> dict:
    """Every end-to-end metric the harness knows, from the client side."""
    toks = sum(n for r in reqs.values() for t, n in r.deliveries if t < seconds)
    ttft, tpot, gaps = [], [], []
    for r in reqs.values():
        if r.due_s >= seconds:
            continue
        ts = [t for t, _ in r.deliveries]
        if ts:
            ttft.append(ts[0] - r.due_s)
            gaps += [b - a for a, b in zip(ts, ts[1:])]
            if r.n_tokens > 1:
                tpot.append((ts[-1] - ts[0]) / (r.n_tokens - 1))
    out = {"tok_s": toks / seconds}
    for name, xs, q in (("ttft_p90_ms", ttft, 0.9), ("tpot_p90_ms", tpot, 0.9),
                        ("gap_p99_ms", gaps, 0.99)):
        v = quantile(xs, q)
        if v is not None:
            out[name] = 1e3 * v
    return out


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader may read (``bench/metrics/*.py``)."""

    cell: object
    seconds: float  # the measured window
    run_s: float  # window plus the drain of requests due in it
    idle_s: float  # of run_s, waiting with no request in flight
    reqs: dict  # rid -> Req (client side)
    server: object  # the program's ServerStats
    spec: object  # the program's SpecStats
    trace: dict | None  # xtrace.reduce of the traced run, else None
    target: object  # the target's roofline (its layout's ``roofline``)
    draft: object  # the draft's roofline
    n_target: int  # chips of the target (1 when colocated)
    n_draft: int  # chips of the draft (0 when colocated)
    peak: dict  # peaks.json entry of the device
    mean_plen: float  # mean prefix length of occupied rows over deliveries

    @property
    def chips(self) -> int:
        return self.n_target + self.n_draft


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def check(pair: Pair, config: dict, mix: dict, items, results: dict, seed: int,
          control: bool = False, root: Path = ROOT) -> dict:
    """Widest gap, over a seeded sample of finished requests (the longest
    among them), by which a served token's logit lies below the float32
    reference's best at its position.  ``control``: also the widest gap of
    the tokens the control (``reference.hidden(control=True)``) puts first at
    the same positions."""
    import jax.numpy as jnp
    import numpy as np

    from bench.traffic.generate import cache_rows, rng

    target, draft = models(config, root)
    ref, tcfg = target.ref, target.hf
    by_rid = {it.rid: it for it in items}
    done = sorted(results, key=lambda rid: (-len(results[rid]), rid))
    k = int(mix["check_requests"])
    sample = done[:1]
    rest = done[1:]
    if rest:
        pick = rng(seed, 3).choice(len(rest), size=min(k - 1, len(rest)), replace=False)
        sample += [rest[i] for i in sorted(pick)]
    T = cache_rows(mix, config["program"]["bs"])
    toks = np.zeros((k, T), np.int32)
    nxt = np.zeros((k, T), np.int32)
    valid = np.zeros((k, T), bool)
    short = 0
    for b, rid in enumerate(sample):
        prompt, served = by_rid[rid].prompt, results[rid]
        short += len(served) != by_rid[rid].max_new
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks[b, : len(seq)] = seq
        lo = len(prompt) - 1
        nxt[b, lo: lo + len(served)] = served
        valid[b, lo: lo + len(served)] = True
    jtoks, jnxt = jnp.asarray(toks), jnp.asarray(nxt)
    h = ref.hidden(pair.tplain, tcfg, jtoks)
    gap, top = ref.head(h, pair.tplain, tcfg, jnxt)
    gap, top = np.asarray(gap)[valid], np.asarray(top)[valid]
    out = {"max_gap": float(gap.max()) if gap.size else float("inf"),
           "limit": float(config["check"]["max_gap"]),
           "tokens": int(valid.sum()), "requests": len(sample), "short": short,
           "differ": int((top != nxt[valid]).sum())}
    if control:
        hc = ref.hidden(pair.tplain, tcfg, jtoks, control=True)
        _, ctop = ref.head(hc, pair.tplain, tcfg, jnxt, control=True)
        cgap, _ = ref.head(h, pair.tplain, tcfg, ctop)
        cgap = np.asarray(cgap)[valid]
        out["control_max_gap"] = float(cgap.max()) if cgap.size else float("inf")
        out["control_differ"] = int((np.asarray(ctop)[valid] != nxt[valid]).sum())
    if pair.dplain is not None and valid.any():
        dref, dcfg = draft.ref, draft.hf
        _, dtop = dref.head(dref.hidden(pair.dplain, dcfg, jtoks), pair.dplain, dcfg, jnxt)
        out["plant"] = plant_readings(np.asarray(dtop) == nxt, pair.pi[toks] == nxt, valid,
                                      depth=1 + config["program"]["d"])
    return out


def plant_readings(agree, on_map, valid, depth: int) -> dict:
    """What the planted pair delivered on the served tokens of the checked
    requests: the share where the draft's greedy token (float32 reference)
    is the served one, the share where the served token follows the
    target's planted map, and the tokens per round of a round that verifies
    the draft's greedy chain ``depth`` tokens deep (accepted plus the bonus
    token) on those same positions."""
    rounds = tokens = 0
    for a, v in zip(agree, valid):
        a = a[v]
        p = 0
        while p < len(a):
            n = 0
            while n < depth and p + n < len(a) and a[p + n]:
                n += 1
            p += n + 1
            rounds, tokens = rounds + 1, tokens + n + 1
    return {"draft_agreement": float(agree[valid].mean()),
            "target_on_map": float(on_map[valid].mean()),
            "chain_tokens_per_round": tokens / max(rounds, 1), "chain_depth": depth}


# ---------------------------------------------------------------------------

def run(root: Path, workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, t_start: float = T_START) -> dict:
    import jax

    from bench import roofline, spec, xtrace
    from bench.obs import CompileCounter, ProfilerTracer, annotate
    from bench.traffic.generate import make_items

    cell = spec.load_cell(root, workload)
    devs = check_device(cell.chips, require_tpu)
    enable_cache(root)
    config, mix = cell.config, cell.mix
    kind = devs[0].device_kind
    peak = roofline.peaks(kind) if require_tpu else None

    target, draft = (m.layout.roofline(m.hf) for m in models(config, root))
    pair = build_pair(build(config, mix, root), config, seed, root)
    log(f"bench: {workload} on {len(devs)} x {kind}; target {target.params} "
        f"params over {pair.n_target} chip(s), draft on {pair.n_draft or 'the same'}")
    warm = warm_up(pair, mix, config["vocab_size"])
    log(f"bench: warm-up {warm}")
    items = make_items(mix, config["vocab_size"], seed, seconds)
    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    marks = {}

    def on_open():
        gc.collect()
        marks["setup_s"] = time.perf_counter() - t_start
        counter.armed = True
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        marks["window"] = annotate("window")
        marks["window"].__enter__()

    rt, reqs, clock, results, queue = serve(
        pair, mix, items, seconds, tracer=ProfilerTracer() if trace else None, on_open=on_open)
    run_s = clock.now()
    marks["window"].__exit__(None, None, None)
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    counter.close()
    log(f"bench: compilations inside the window: {counter.compiles} "
        f"(traces {counter.traces}) {sorted(set(counter.names))}")

    mem = [d.memory_stats() or {} for d in devs[: cell.chips]]
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": max(int(m.get("peak_bytes_in_use", 0)) for m in mem)}

    due = [r for r in reqs.values() if r.due_s < seconds]
    attempted = len(due)
    failed = sum(1 for r in due if r.rid not in results or r.n_tokens != r.max_new)
    late = [queue.entered[r.rid] - r.due_s for r in due if r.rid in queue.entered]
    server, spec_stats = rt.stats, rt.stepper.spec_stats
    idle_s = clock.idle_s
    deliveries = [(r.plen, r.deliveries) for r in reqs.values()]
    del rt
    gc.collect()

    red = None
    if trace:
        rec = xtrace.extract(xtrace.find_xplane(trace_dir))
        red = xtrace.reduce(rec)
        red["busy_mean_s"] = xtrace.mean_busy(rec)
        log(f"bench: busy seconds by device {xtrace.busy_by_device(rec)} of a "
            f"{red['window_s']!r} s window")
        draft_dev = next((k for k, v in sorted(rec["devices"].items()) if k != red["device"]
                          and any(m[0] == "jit_expand" for m in v["modules"])), None)
        red["draft_programs"] = (xtrace.reduce(rec, device=draft_dev)["programs"]
                                 if draft_dev else red["programs"])
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)

    plens = []
    for plen, dl in deliveries:
        n = 0
        for _, k in dl:
            plens.append(plen + n)
            n += k
    data = RunData(cell=cell, seconds=seconds, run_s=run_s, idle_s=idle_s, reqs=reqs,
                   server=server, spec=spec_stats, trace=red,
                   target=target, draft=draft,
                   n_target=pair.n_target, n_draft=pair.n_draft, peak=peak,
                   mean_plen=statistics.fmean(plens) if plens else 0.0)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = spec.load_reader(root, m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(reqs, seconds)
        e2e["setup_s"] = marks["setup_s"]
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    res = check(pair, config, mix, items, results, seed, root=root)
    correct = res["max_gap"] <= res["limit"] and res["short"] == 0 and res["requests"] > 0
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["device"]["busy_s"] = red["busy_mean_s"]
        out["device"]["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    out["setup_s"] = marks["setup_s"]
    out["generator_late_ms"] = {"p50": 1e3 * (quantile(late, 0.5) or 0.0),
                                "max": 1e3 * max(late, default=0.0)}
    out["compiles_in_window"] = counter.compiles
    out["warm_up"] = warm
    out["plant"] = res.get("plant")
    out["check"] = {"max_gap": res["max_gap"], "max_gap_limit": res["limit"],
                    "short_requests": res["short"], "short_requests_limit": 0,
                    "tokens_compared": res["tokens"], "requests_compared": res["requests"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoDevice as e:
        log(f"bench: {e}")
        return 2
    c = out["check"]
    log(f"check: max_gap {c['max_gap']!r} limit {c['max_gap_limit']!r} "
        f"({c['tokens_compared']} tokens of {c['requests_compared']} requests)")
    log(f"check: short_requests {c['short_requests']} limit {c['short_requests_limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
