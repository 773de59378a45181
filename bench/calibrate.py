"""Readings that set a cell's limits and rates, on the chip (not part of a
benchmark run).

    python bench/calibrate.py readings --workload coder33b-l6.single --seeds 1,2,3 --seconds 18
        per seed: the program's widest gap against the float32 reference and
        the control's (float8 weights), on the same prompts and tokens, and
        what the planted pair delivered (``run.plant_readings``), from one
        engine in one process
    python bench/calibrate.py knee --workload coder33b-l6.chat --seeds 1,2 --rates 0.4,0.6 --seconds 51
        the chat mix served at each offered rate, for each seed: client-side
        tails, the drain after the window and the tokens completed per second
    python bench/calibrate.py tracedump --workload coder33b-l6.single --seconds 5 --record r.json
        the profiler trace's planes and lines, with sample events; with
        ``--record`` also the first quarter second of the window as the
        compact record ``xtrace.reduce`` reads

Each prints one JSON line per reading and appends it to ``--out``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def emit(out: str | None, rec: dict) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        with open(out, "a") as f:
            f.write(line + "\n")


def readings(cell, seeds, seconds, out):
    eng = R.build(cell.config, cell.mix)
    warm = None
    for seed in seeds:
        pair = R.build_pair(eng, cell.config, seed)
        if warm is None:
            warm = R.warm_up(pair, cell.mix, cell.config["vocab_size"])
        emit(out, {"workload": cell.name, "seed": seed,
                   **reading(pair, cell.config, cell.mix, seed, seconds)})
        del pair
        gc.collect()


def reading(pair, config, mix, seed, seconds) -> dict:
    """One seed served for ``seconds``, then the program's and the control's
    widest gaps and the planted pair's readings."""
    from bench.traffic.generate import make_items

    items = make_items(mix, config["vocab_size"], seed, seconds)
    t0 = time.perf_counter()
    rt, reqs, clock, results, _ = R.serve(pair, mix, items, seconds)
    run_s = clock.now()
    recs = list(rt.stats.records.values())
    rounds = sum(r.n_rounds for r in recs)
    spec = rt.stepper.spec_stats
    del rt
    gc.collect()
    t1 = time.perf_counter()
    res = R.check(pair, config, mix, items, results, seed, control=True)
    return {"seconds": seconds, "run_s": run_s, "serve_wall_s": t1 - t0,
            "check_wall_s": time.perf_counter() - t1,
            "tokens_per_round": sum(r.n_tokens for r in recs) / max(rounds, 1),
            "commit_rate": spec.spec_commits / max(spec.spec_rounds, 1),
            "distinct_share": [len(set(v)) / max(len(v), 1) for v in results.values()],
            **R.end_to_end(reqs, seconds), **res}


def knee(cell, rates, seconds, seeds, out):
    from bench.traffic.generate import make_items

    eng = R.build(cell.config, cell.mix)
    warm = None
    for seed in seeds:
        pair = R.build_pair(eng, cell.config, seed)
        if warm is None:
            warm = R.warm_up(pair, cell.mix, cell.config["vocab_size"])
        for rate in rates:
            mix = dict(cell.mix, rate_rps=rate)
            items = make_items(mix, cell.config["vocab_size"], seed, seconds)
            rt, reqs, clock, results, queue = R.serve(pair, mix, items, seconds)
            run_s = clock.now()
            done = sum(r.n_tokens for r in reqs.values())
            emit(out, {"workload": cell.name, "seed": seed, "rate_rps": rate, "seconds": seconds,
                       "requests": len(items), "rejected": queue.rejected,
                       "offered_tok_s": sum(it.max_new for it in items) / seconds,
                       "drain_s": run_s - seconds, "completed_tok_s": done / run_s,
                       "rounds": rt.stats.rounds, **R.end_to_end(reqs, seconds)})
            del rt
            gc.collect()
        del pair
        gc.collect()


def tracedump(cell, seconds, seed, out, record=None, record_s=0.25):
    import tempfile

    import jax
    from jax.profiler import ProfileData

    from bench import xtrace
    from bench.obs import ProfilerTracer, annotate
    from bench.traffic.generate import make_items

    eng = R.build(cell.config, cell.mix)
    pair = R.build_pair(eng, cell.config, seed)
    R.warm_up(pair, cell.mix, cell.config["vocab_size"])
    items = make_items(cell.mix, cell.config["vocab_size"], seed, seconds)
    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    with annotate("window"):
        R.serve(pair, cell.mix, items, seconds, tracer=ProfilerTracer())
    jax.profiler.stop_trace()
    path = xtrace.find_xplane(d)
    emit(out, {"xplane_bytes": Path(path).stat().st_size})
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            emit(out, {"plane": plane.name, "line": line.name, "events": len(evs),
                       "sample": [[e.name, e.start_ns, e.duration_ns,
                                   [[k, str(v)] for k, v in e.stats][:6]] for e in evs[:4]]})
    t = time.perf_counter()
    rec = xtrace.extract(path)
    red = xtrace.reduce(rec)
    red["extract_s"] = time.perf_counter() - t
    red.pop("programs")
    emit(out, red)
    if record:
        # the window's first ``record_s`` seconds, as the compact record the
        # tests reduce (bench/tests/data/tpu_trace.json)
        t0 = rec["t0"]
        t1 = t0 + int(record_s * 1e9)
        keep = lambda evs: [e for e in evs if t0 <= e[1] < t1]  # noqa: E731
        small = {"t0": t0, "t1": t1, "host": [["bench.window", t0, t1 - t0]]
                 + [h for h in keep(rec["host"]) if h[0] != "bench.window"],
                 "devices": {k: {"modules": keep(v["modules"])}
                             for k, v in rec["devices"].items()}}
        Path(record).parent.mkdir(parents=True, exist_ok=True)
        Path(record).write_text(json.dumps(small))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "knee", "tracedump"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--record", default=None, help="tracedump: write a slice of the compact record here")
    args = ap.parse_args(argv)
    from bench import spec

    cell = spec.load_cell(R.ROOT, args.workload)
    R.check_device(cell.chips)
    R.enable_cache(R.ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.mode == "readings":
        readings(cell, seeds, args.seconds, args.out)
    elif args.mode == "knee":
        knee(cell, [float(r) for r in args.rates.split(",")], args.seconds, seeds, args.out)
    else:
        tracedump(cell, args.seconds, seeds[0], args.out, record=args.record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
