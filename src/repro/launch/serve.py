"""Serving driver: asynchronous disaggregated speculative decoding
(the paper's system, end to end).

  PYTHONPATH=src python -m repro.launch.serve --requests 3 --max-new 48
  PYTHONPATH=src python -m repro.launch.serve --continuous --requests 8
  PYTHONPATH=src python -m repro.launch.serve --continuous --replicas 2

Runs the profile pass (paper §5.5: allocation split + expansion depth d),
then serves a deterministic request stream through SpecEngine and reports
decoding speed + compression ratio per request.  ``--continuous`` replaces
the one-batch-at-a-time replay with the continuous-batching runtime
(repro.serving): a seeded Poisson arrival trace is served through per-slot
request lifecycles — admissions backfill retiring slots mid-flight, per
request telemetry (TTFT, tok/s, acceptance, overlapping round lifetimes) is
printed, and each finished output is checked byte-identical against the same
request served alone (--no-verify to skip).  ``--replicas N`` shards the
continuous runtime over N SpecEngine replicas on disjoint device groups
(one global queue, least-loaded routing, per-replica + fleet telemetry).
``--async-rounds`` turns on asynchronous round disaggregation
(docs/async-disaggregation.md): each replica drafts round N+1's tree while
round N verifies, reconciling on a rejected lookahead seed — outputs stay
byte-identical to lockstep, and the traced ``draft_lookahead`` /
``verify_dispatch`` overlap in the phase breakdown is the evidence.
``--adaptive-depth`` turns on per-slot adaptive draft depth and
``--deadline-s X`` stamps every request with a finish deadline X seconds
after its arrival — EDF queueing, slack-aware routing, and an SLO
attainment report (docs/scheduling.md); outputs stay byte-identical.
``--trace-out trace.json --metrics-out metrics.json`` records per-round
phase spans (draft expand / verify / sync / reroot / absorb — viewable in
ui.perfetto.dev) and a metrics snapshot with the round-time decomposition
(repro.obs, docs/observability.md).
``--n-target``/``--n-draft`` select the disaggregated split carved once per
replica (by default derived from the device count: on one device target and
draft share it).  chip_smoke.py builds the published-width pair through the
same ``serving_configs`` + ``build_engine``.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from pathlib import Path

import jax
import numpy as np

from repro.configs import ModelConfig, get_config
from repro.core.engine import SpecConfig, SpecEngine
from repro.core.scheduler import candidate_depths, profile_times
from repro.data import make_request_stream, make_request_trace
from repro.launch.mesh import make_serving_mesh
from repro.models.api import make_model
from repro.obs.clock import monotonic
from repro.sharding import sharding_for_tree


def enable_compile_cache() -> None:
    """Persistent compilation cache for an entry point: JAX's own
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else a fixed directory in
    the checkout (a fixed path, so the next run finds what this one wrote)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return  # JAX reads the variable itself
    root = Path(__file__).resolve().parents[3]
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))


def serving_configs(target_arch: str, draft_arch: str, *, smoke: bool = True,
                    target_layers: int = 0, dtype: str | None = None):
    """(target, draft) ModelConfigs for serving: the smoke shapes by default
    (CPU), or the published widths with ``smoke=False``.  ``target_layers``
    cuts only the target's depth (what one chip holds); ``dtype`` sets the
    compute and parameter dtype of both (e.g. "bfloat16" on a TPU)."""
    cfgT = get_config(target_arch, smoke=smoke)
    cfgD = get_config(draft_arch, smoke=smoke)
    if target_layers:
        cfgT = dataclasses.replace(cfgT, n_layers=target_layers)
    if dtype:
        cfgT = dataclasses.replace(cfgT, dtype=dtype, param_dtype=dtype)
        cfgD = dataclasses.replace(cfgD, dtype=dtype, param_dtype=dtype)
    return cfgT, cfgD


def init_params(model, key, mesh, *, peaked: bool = True):
    """Seeded random parameters, initialised directly in their sharded layout
    on ``mesh`` (``sharding_for_tree``), so no full copy ever sits on one
    device.  ``peaked`` scales the lm_head by 4: random-init logits are
    near-uniform, and peaked greedy chains give realistic acceptance."""

    def make(k):
        p = model.init(k)
        if peaked:
            p["lm_head"].value = p["lm_head"].value * 4.0
        return p

    shardings = sharding_for_tree(mesh, jax.eval_shape(make, key))
    return jax.jit(make, out_shardings=shardings)(key)


def place_params(params, mesh):
    """Copy a Param tree onto ``mesh`` in its sharded layout."""
    return jax.device_put(params, sharding_for_tree(mesh, params))


def build_engine(cfgT: ModelConfig, cfgD: ModelConfig, *, mode="parallel",
                 bs=8, w=4, c=2, d=2, max_new=48, S_max=512, n_target=None, n_draft=None,
                 peaked=True, replicas=1, async_rounds=False):
    """Build the serving engine(s) for a (target, draft) config pair.

    The devices are carved by ``make_serving_mesh`` (``n_target``/``n_draft``
    per replica; None derives the split from the device count, colocating
    both models on a one-device replica).  Each replica gets its own
    SpecEngine with parameters placed on its own target and draft meshes.
    Returns (engine, tparams, dparams, cfgT) for ``replicas == 1`` and
    ([engines], [tparams], [dparams], cfgT) otherwise."""
    assert cfgT.vocab_size == cfgD.vocab_size, "draft/target must share a vocab"
    T, D = make_model(cfgT), make_model(cfgD)
    cfg = SpecConfig(bs=bs, w=w, c=c, d=d, mode=mode, max_new=max_new,
                     async_rounds=async_rounds)
    pairs = make_serving_mesh(n_target, n_draft, replicas=replicas)
    pairs = [pairs] if replicas == 1 else pairs
    kt, kd = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    engines, tps, dps = [], [], []
    for mesh_t, mesh_d in pairs:
        if not tps:
            tp = init_params(T, kt, mesh_t, peaked=peaked)
            dp = init_params(D, kd, mesh_d, peaked=peaked)
        else:  # the same weights, copied onto this replica's devices
            tp, dp = place_params(tps[0], mesh_t), place_params(dps[0], mesh_d)
        engines.append(SpecEngine(T, D, cfg, S_max_t=S_max, S_max_d=S_max,
                                  mesh_target=mesh_t, mesh_draft=mesh_d))
        tps.append(tp)
        dps.append(dp)
    if replicas == 1:
        return engines[0], tps[0], dps[0], cfgT
    return engines, tps, dps, cfgT


def run_continuous(args, engines, tp, dp, cfgT) -> None:
    """Continuous batching: serve a Poisson trace with per-slot lifecycles,
    on one engine or a sharded fleet (``--replicas N``).  With
    ``--trace-out``/``--metrics-out`` the run is instrumented end to end
    (repro.obs): per-round phase spans land in a Chrome/Perfetto-viewable
    ``trace.json`` (or JSONL), the metrics snapshot (per-replica round
    counters, accepted-depth histogram, TTFT, queue depth over time) plus
    the draft/verify/absorb round decomposition land in the metrics JSON."""
    from repro.obs import MetricsRegistry, Tracer, breakdown_report, phase_breakdown
    from repro.serving import (ContinuousBatchingRuntime, Request, RequestQueue,
                               SchedulerConfig, ShardedServingRuntime, WallClock)

    observed = bool(args.trace_out or args.metrics_out)
    tracer = Tracer() if observed else None
    metrics = MetricsRegistry() if observed else None
    scheduler = SchedulerConfig() if args.adaptive_depth else None

    trace = make_request_trace(
        cfgT.vocab_size, args.requests, rate_rps=args.rate,
        prompt_len=(max(4, args.prompt_len // 2), args.prompt_len),
        max_new=args.max_new, seed=0,
    )
    if isinstance(engines, list):
        rt = ShardedServingRuntime(
            engines, tp, dp, n_slots=args.slots,
            queue=RequestQueue(cap=args.queue_cap), clock=WallClock(),
            tracer=tracer, metrics=metrics, scheduler=scheduler,
        )
        label = f"{len(engines)} replicas x {args.slots} slots"
    else:
        rt = ContinuousBatchingRuntime(
            engines, tp, dp, n_slots=args.slots,
            queue=RequestQueue(cap=args.queue_cap), clock=WallClock(),
            tracer=tracer, metrics=metrics, scheduler=scheduler,
        )
        label = f"{args.slots} slots"
    accepted = rt.submit_trace(
        Request(rid=r.rid, prompt=r.prompt, arrival_s=r.arrival_s, max_new=r.max_new,
                deadline_s=(r.arrival_s + args.deadline_s) if args.deadline_s else None)
        for r in trace
    )
    print(f"continuous: {accepted}/{len(trace)} requests accepted "
          f"({label}, Poisson rate {args.rate}/s, queue cap {args.queue_cap}"
          + (f", deadline {args.deadline_s}s" if args.deadline_s else "")
          + (", adaptive depth" if scheduler else "") + ")")
    t0 = monotonic()
    results = rt.run()
    wall = monotonic() - t0
    print(rt.report() if isinstance(engines, list) else rt.stats.report())
    total = sum(len(v) for v in results.values())
    print(f"wall: {total} tokens in {wall:.1f}s ({total/wall:.1f} tok/s incl. compile); "
          f"{rt.queue.rejected} shed by admission control")

    summary = rt.summary() if isinstance(engines, list) else rt.stats.summary()
    if summary["n_deadlined"]:
        print(f"SLO: {summary['slo_attainment']:.0%} of {summary['n_deadlined']} "
              f"deadlined requests met (slack p50 {summary['slack_p50_s']:+.3f}s "
              f"p10 {summary['slack_p10_s']:+.3f}s)")

    if observed:
        bd = phase_breakdown(tracer)
        print(breakdown_report(bd))
        if tracer.dropped:
            print(f"trace ring buffer dropped {tracer.dropped} events")
        if args.trace_out:
            path = tracer.write(args.trace_out)
            print(f"trace -> {path} (open in ui.perfetto.dev or chrome://tracing)")
        if args.metrics_out:
            slo = {k: summary[k] for k in ("n_deadlined", "slo_attainment",
                                           "slack_p50_s", "slack_p10_s")}
            path = metrics.write(args.metrics_out,
                                 extra={"phase_breakdown": bd, "slo": slo})
            print(f"metrics -> {path}")

    if args.verify:
        mismatches = verify_served_alone(rt, engines, tp, dp, trace, results, args.slots)
        if mismatches:
            raise SystemExit(f"{mismatches} request(s) diverged from their solo run")


def verify_served_alone(rt, engines, tp, dp, trace, results, n_slots: int) -> int:
    """Check each served output byte-identical to the same request served
    alone: on the replica (engine, meshes and params) that served it, in a
    runtime of the same slot count, with no other request in flight.  The
    programs and batch width are the same; only the neighbours and the
    admission timing differ, and they must never change a request's tokens.
    (A batch-1 ``generate()`` is no such reference on a TPU: its round
    programs round bf16 differently from batch-2 ones.)  Prints one line per
    request and returns the number of mismatches."""
    from repro.serving import ContinuousBatchingRuntime, Request

    fleet = isinstance(engines, list)
    mismatches = 0
    for r in trace:
        if r.rid not in results:
            continue
        i = rt.replica_of(r.rid) if fleet else 0
        eng, t, d = (engines[i], tp[i], dp[i]) if fleet else (engines, tp, dp)
        alone = ContinuousBatchingRuntime(eng, t, d, n_slots=n_slots)
        alone.submit(Request(rid=r.rid, prompt=r.prompt, arrival_s=0.0, max_new=r.max_new))
        solo, got = alone.run()[r.rid], results[r.rid]
        where = f" (replica {i})" if fleet else ""
        if got == solo:
            print(f"verify req {r.rid}: byte-identical to its solo run{where}")
            continue
        mismatches += 1
        p = next((j for j, (a, b) in enumerate(zip(got, solo)) if a != b), min(len(got), len(solo)))
        print(f"verify req {r.rid}: MISMATCH at token {p} of {len(got)}/{len(solo)}{where}")
    return mismatches


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--target-arch", default="qwen2.5-14b")
    ap.add_argument("--draft-arch", default="qwen2.5-14b")
    ap.add_argument("--mode", choices=["parallel", "serial"], default="parallel")
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--bs", type=int, default=8)
    ap.add_argument("--w", type=int, default=4)
    ap.add_argument("--d", type=int, default=0, help="0 = profile-derived")
    ap.add_argument("--n-target", type=int, default=None,
                    help="target devices per replica (default: from the device count)")
    ap.add_argument("--n-draft", type=int, default=None,
                    help="draft devices per replica; 0 colocates with the target")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a Poisson trace through the continuous-batching runtime")
    ap.add_argument("--async-rounds", action="store_true",
                    help="asynchronous round disaggregation: draft round N+1's "
                         "tree on the draft mesh while round N verifies on the "
                         "target mesh (parallel mode only; outputs stay "
                         "byte-identical to lockstep)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="continuous: SpecEngine replicas on disjoint device groups "
                         "(one global queue, depth-aware routing)")
    ap.add_argument("--slots", type=int, default=2, help="continuous: engine batch slots")
    ap.add_argument("--rate", type=float, default=2.0, help="continuous: Poisson arrival rate (req/s)")
    ap.add_argument("--queue-cap", type=int, default=64, help="continuous: admission-control queue cap")
    ap.add_argument("--adaptive-depth", action="store_true",
                    help="continuous: per-slot adaptive draft depth — each "
                         "slot's measured-acceptance EMA picks a depth bucket; "
                         "the round runs at the max over occupied slots "
                         "(docs/scheduling.md; outputs stay byte-identical)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="continuous: per-request finish deadline, seconds "
                         "after arrival (0 = best-effort); enables EDF "
                         "queueing, slack-aware routing, and SLO reporting")
    ap.add_argument("--no-verify", dest="verify", action="store_false",
                    help="continuous: skip the byte-identical check against each "
                         "request served alone")
    ap.add_argument("--trace-out", default=None,
                    help="continuous: write phase spans here (.json = Chrome/"
                         "Perfetto traceEvents, .jsonl = span per line)")
    ap.add_argument("--metrics-out", default=None,
                    help="continuous: write the metrics snapshot + phase "
                         "breakdown here (.json; .prom = Prometheus text)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    replicas = args.replicas if args.continuous else 1
    cfgT, cfgD = serving_configs(args.target_arch, args.draft_arch)
    eng, tp, dp, cfgT = build_engine(
        cfgT, cfgD, mode=args.mode, bs=args.bs, w=args.w,
        d=args.d or 2, max_new=args.max_new, n_target=args.n_target, n_draft=args.n_draft,
        replicas=replicas, async_rounds=args.async_rounds,
    )
    eng0, tp0, dp0 = (eng[0], tp[0], dp[0]) if isinstance(eng, list) else (eng, tp, dp)

    # profile pass (paper §5.5): pick d from measured draft/target times
    if args.d == 0:
        prompt = np.zeros((1, args.prompt_len), np.int32)
        prof = eng0.profile(tp0, dp0, prompt)
        d_lo, d_hi = candidate_depths(prof)
        d_cfg = dataclasses.replace(eng0.cfg, d=d_lo)
        for e in set(eng) if isinstance(eng, list) else {eng}:
            e.cfg = d_cfg
        print(f"profile: t_draft={prof.t_draft_s*1e3:.1f}ms t_target={prof.t_target_s*1e3:.1f}ms "
              f"-> d in {{{d_lo},{d_hi}}}, using d={d_lo}")

    if args.continuous:
        run_continuous(args, eng, tp, dp, cfgT)
        return

    total_toks, total_s = 0, 0.0
    sess = eng0.session(tp0, dp0)
    for i, prompt in enumerate(make_request_stream(cfgT.vocab_size, args.prompt_len, 1, args.requests)):
        t0 = monotonic()
        out, stats = sess.generate(prompt)
        dt = monotonic() - t0
        total_toks += len(out[0])
        total_s += dt
        print(f"req {i}: {len(out[0])} tokens in {dt:.2f}s "
              f"({len(out[0])/dt:.1f} tok/s), compression {stats.compression_ratio:.2f}")
    print(f"aggregate: {total_toks/total_s:.1f} tokens/s ({args.mode} mode)")


if __name__ == "__main__":
    main()
