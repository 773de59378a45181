"""Bring-up smoke: the speculative serving path on a TPU, at the published
widths of the DeepSeek-Coder-33B target / DeepSeek-Coder-1.3B draft pair.

    python chip_smoke.py             # one chip: phases 1-3
    python chip_smoke.py --chips 4   # a four-chip host: the multi-chip phase only

Everything goes through the entry points a user calls: ``build_engine``
(launch/serve.py), ``EngineSession.generate``, and the continuous-batching
runtimes.  The weights are random, made from a seed; bf16 throughout.  The
target keeps its published widths (d_model 7168, 56 query / 8 KV heads of
128, d_ff 19200) with its depth cut to what one 16 GB chip holds beside the
whole 24-layer draft.

One chip:
  1. solo generate (the pipelined round) against target-only greedy
     decoding of the same weights (the tie rule below);
  2. continuous serving (WallClock, 2 slots): every output
     byte-identical to the same request served alone (same engine, same
     slot count), and within the tie rule of target-only greedy decoding;
  3. phase 1 again with the Pallas kernels on; the compiled round programs
     must hold each kernel as a ``tpu_custom_call``.
Four chips (``--chips 4``): two replicas of 1+1 chips behind the sharded
runtime, then one replica with target TP=2 and draft TP=2 on disjoint chips,
kernels on, each checked byte-identical against every request served alone
on its own meshes and against the one-device greedy oracle.

Any failure raises and exits non-zero.  Wall times include compilation and
are no throughput figures.  The last line of output is one JSON object that
names the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TARGET, DRAFT = "deepseek-coder-33b", "deepseek-coder-1.3b"
TARGET_LAYERS = 6  # ~7.3 GB of target + ~2.7 GB of draft on a 16 GB chip
ENGINE = dict(bs=8, w=4, c=2, d=2, S_max=256)
PROMPT_LEN = 16
KERNELS = dict(use_pallas_attention=True, use_pallas_swiglu=True, use_pallas_kv_moves=True)
# The speculative stream may leave the oracle's only at a step where the
# oracle's own top two logits are within this fraction of the top one: the
# two paths compute the same bf16 activations in different batch shapes and
# orders, which moves a logit by a few bf16 rounding steps (bf16 keeps 8
# significant bits; 2**-6 is two to four of its steps).
TIE_REL = 2.0 ** -6
# kernels each compiled round program must hold (phase 3)
EXPECTED_KERNELS = {
    "_verify": {"tree_attention", "fused_swiglu"},
    "_expand": {"tree_attention", "fused_swiglu"},
    "_compact": {"kv_move_rows"},
    "_spec_kv_move": {"kv_move_rows"},
    "_install": {"slot_write_rows"},
}
_CUSTOM_CALL = re.compile(r"%([A-Za-z_]+?)(?:\.\d+)* = .*custom_call_target=\"tpu_custom_call\"")


def log(msg: str = "") -> None:
    print(msg, flush=True)


def pair_configs():
    from repro.launch.serve import serving_configs

    return serving_configs(TARGET, DRAFT, smoke=False, target_layers=TARGET_LAYERS,
                           dtype="bfloat16")


def param_bytes(params) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(params))


def fingerprint(params) -> int:
    """Order-free checksum of a parameter tree (wrapping integer sum of the
    raw bits), equal for equal weights however they are sharded."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        return jax.lax.bitcast_convert_type(x, u).astype(jnp.uint32).sum(dtype=jnp.uint32)

    return sum(int(jax.device_get(bits(x))) for x in jax.tree.leaves(params)) % 2**32


def prompts(vocab: int, n: int, seed: int):
    import numpy as np

    return np.random.default_rng(seed).integers(0, vocab, size=(n, PROMPT_LEN), dtype=np.int32)


def check_tie_rule(label: str, rows, oracle) -> None:
    """Each row must equal the oracle's greedy tokens up to its first
    divergence, and diverge only where the oracle's top two logits tie."""
    toks, top2 = oracle
    for b, got in enumerate(rows):
        want = [int(t) for t in toks[b]]
        if len(got) != len(want):
            raise AssertionError(f"{label} row {b}: {len(got)} tokens, oracle {len(want)}")
        p = next((i for i, (g, w) in enumerate(zip(got, want)) if g != w), None)
        if p is None:
            log(f"  {label} row {b}: {len(got)}/{len(want)} tokens equal to greedy")
            continue
        hi, lo = (float(v) for v in top2[b, p])
        gap, tol = hi - lo, TIE_REL * abs(hi)
        log(f"  {label} row {b}: equal to greedy for {p} tokens; at {p} got {got[p]}, "
            f"greedy {want[p]}, top-2 gap {gap!r} (tie: <= {tol!r})")
        if gap > tol:
            raise AssertionError(f"{label} row {b}: left greedy at {p} with no bf16 tie")


def fresh_engine(eng):
    """The same model pair, SpecConfig and meshes with fresh jitted programs
    (they trace the flags active at their first call)."""
    from repro.core.engine import SpecEngine

    return SpecEngine(eng.target, eng.draft, eng.cfg,
                      S_max_t=eng.S_max_t, S_max_d=eng.S_max_d,
                      mesh_target=eng.mesh_target, mesh_draft=eng.mesh_draft)


def timed_generate(label, eng, tp, dp, prompt, oracle, device):
    from repro.obs.clock import monotonic

    sess = eng.session(tp, dp)
    t0 = monotonic()
    out, stats = sess.generate(prompt)
    wall = monotonic() - t0
    for b, row in enumerate(out):
        log(f"  {label} request {b}: {len(row)} tokens, batch wall {wall!r} s on "
            f"{device} (includes compilation), {stats.rounds} rounds")
    check_tie_rule(label, out, oracle)
    return sess


def kernels_in(text: str) -> set:
    return {m.group(1) for m in _CUSTOM_CALL.finditer(text)}


def check_kernel_programs(eng, tp, dp, state) -> None:
    """Compile the round programs under the kernel flags and find each
    expected Pallas kernel in the compiled text as a tpu_custom_call."""
    import jax
    import jax.numpy as jnp

    from repro.flags import override_flags
    from repro.sharding import SERVING_RULES, use_mesh

    def shapes(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
                            tree)

    tc, dc, tr, plan = (shapes(x) for x in (state.tcache, state.dcache, state.tr, state.plan))
    B, n = plan.tokens.shape
    moves = (jnp.zeros((B, n), jnp.int32), jnp.zeros((B, n), jnp.int32), jnp.zeros((B, n), bool))
    one = jax.eval_shape(lambda: eng.target.init_cache(1, eng.S_max_t))
    progs = {
        "_verify": (eng.mesh_target, (tp, tc, plan.tokens, plan.positions, plan.rows,
                                      plan.mask, plan.parent_pos, plan.valid)),
        "_expand": (eng.mesh_draft, (dp, tr, dc)),
        "_compact": (eng.mesh_target, (tc, *moves)),
        "_spec_kv_move": (eng.mesh_draft, (dc, *moves)),
        "_install": (eng.mesh_target, (tc, one, 0)),
    }
    with override_flags(**KERNELS):
        for name, (mesh, args) in progs.items():
            with use_mesh(mesh, SERVING_RULES):
                text = getattr(eng, name).lower(*args).compile().as_text()
            found = kernels_in(text)
            log(f"  {name}: tpu_custom_call kernels {sorted(found)}")
            missing = EXPECTED_KERNELS[name] - found
            if missing:
                raise AssertionError(f"{name}: no tpu_custom_call for {sorted(missing)}")


def one_chip(max_new: int, device: str) -> None:
    from repro.core.engine import greedy_decode
    from repro.flags import override_flags
    from repro.launch.serve import build_engine

    cfgT, cfgD = pair_configs()
    log(f"target {cfgT.name}: {cfgT.n_layers} of 62 layers, d_model {cfgT.d_model}, "
        f"{cfgT.n_heads}/{cfgT.n_kv_heads} heads of {cfgT.head_dim}, d_ff {cfgT.d_ff}, "
        f"vocab {cfgT.vocab_size}, {cfgT.dtype}")
    log(f"draft  {cfgD.name}: {cfgD.n_layers} of 24 layers, d_model {cfgD.d_model}, "
        f"{cfgD.n_heads}/{cfgD.n_kv_heads} heads of {cfgD.head_dim}, d_ff {cfgD.d_ff}, {cfgD.dtype}")
    eng, tp, dp, _ = build_engine(cfgT, cfgD, n_target=1, n_draft=0, max_new=max_new, **ENGINE)
    log(f"param bytes: target {param_bytes(tp)}, draft {param_bytes(dp)} "
        f"(both on {device}, colocated)")
    prompt = prompts(cfgT.vocab_size, 2, seed=0)
    oracle = greedy_decode(eng.target, tp, prompt, max_new, eng.S_max_t)

    log("phase 1: solo generate vs target-only greedy")
    timed_generate("round", eng, tp, dp, prompt, oracle, device)
    log("phase 1: pass")

    log("phase 2: continuous serving (2 slots, WallClock)")
    served, rows = serve_and_verify(eng, tp, dp, cfgT.vocab_size, max_new, device)
    check_tie_rule("served", rows, greedy_decode(eng.target, tp, served, max_new, eng.S_max_t))
    log("phase 2: pass")

    log("phase 3: Pallas kernels on (tree_attention, fused_swiglu, kv_move_rows, slot_write_rows)")
    with override_flags(**KERNELS):
        k_eng = fresh_engine(eng)
        sess = timed_generate("round+kernels", k_eng, tp, dp, prompt, oracle, device)
    check_kernel_programs(k_eng, tp, dp, sess.state)
    log("phase 3: pass")


def serve_and_verify(engines, tp, dp, vocab: int, max_new: int, device: str, n_req: int = 4):
    """Serve ``n_req`` requests through the continuous runtime (one engine)
    or the sharded runtime (a list); check every output byte-identical to the
    request served alone on the replica that served it.  Returns (prompts,
    rows)."""
    import numpy as np

    from repro.data import make_request_trace
    from repro.launch.serve import verify_served_alone
    from repro.serving import (ContinuousBatchingRuntime, Request, RequestQueue,
                               ShardedServingRuntime, WallClock)

    trace = make_request_trace(vocab, n_req, rate_rps=4.0, prompt_len=(PROMPT_LEN, PROMPT_LEN),
                               max_new=max_new, seed=1)
    fleet = isinstance(engines, list)
    runtime = ShardedServingRuntime if fleet else ContinuousBatchingRuntime
    rt = runtime(engines, tp, dp, n_slots=2, queue=RequestQueue(), clock=WallClock())
    rt.submit_trace(Request(rid=r.rid, prompt=r.prompt, arrival_s=r.arrival_s,
                            max_new=r.max_new) for r in trace)
    results = rt.run()
    stats = rt.stats if fleet else [rt.stats]
    for r in trace:
        i = rt.replica_of(r.rid) if fleet else 0
        rec = stats[i].records[r.rid]
        log(f"  request {r.rid} (replica {i}): {len(results[r.rid])} tokens, "
            f"{rec.finish_s - rec.arrival_s!r} s arrival to finish on {device} "
            f"(includes compilation), {rec.n_rounds} rounds")
    if len(results) != n_req:
        raise AssertionError(f"served {len(results)} of {n_req} requests")
    mismatches = verify_served_alone(rt, engines, tp, dp, trace, results, n_slots=2)
    if mismatches:
        raise AssertionError(f"{mismatches} served output(s) differ from their solo run")
    return np.stack([r.prompt for r in trace]), [results[r.rid] for r in trace]


def four_chips(max_new: int, device: str) -> None:
    import numpy as np

    from repro.core.engine import greedy_decode
    from repro.flags import override_flags
    from repro.launch.serve import build_engine

    cfgT, cfgD = pair_configs()
    with override_flags(**KERNELS):
        log("multi-chip A: 2 replicas of 1 target + 1 draft chip, sharded runtime, kernels on")
        engs, tps, dps, _ = build_engine(cfgT, cfgD, n_target=1, n_draft=1, replicas=2,
                                         max_new=max_new, **ENGINE)
        for i, e in enumerate(engs):
            log(f"  replica {i}: target on {list(e.mesh_target.devices.flat)}, "
                f"draft on {list(e.mesh_draft.devices.flat)}")
        weights = fingerprint(tps[0])
        served_prompts, rows = serve_and_verify(engs, tps, dps, cfgT.vocab_size, max_new, device)
        with override_flags(use_pallas_attention=False, use_pallas_swiglu=False,
                            use_pallas_kv_moves=False):
            oracle = greedy_decode(engs[0].target, tps[0], served_prompts, max_new, engs[0].S_max_t)
        check_tie_rule("2x(1+1)", rows, oracle)
        log("multi-chip A: pass")
        del engs, tps, dps
        gc.collect()

        log("multi-chip B: 1 replica, target TP=2 + draft TP=2 on disjoint chips, kernels on")
        eng, tp, dp, _ = build_engine(cfgT, cfgD, n_target=2, n_draft=2,
                                      max_new=max_new, **ENGINE)
        log(f"  target on {list(eng.mesh_target.devices.flat)}, "
            f"draft on {list(eng.mesh_draft.devices.flat)}")
        if fingerprint(tp) != weights:
            raise AssertionError("TP-sharded init made other weights than the one-chip init")
        served_b, rows_b = serve_and_verify(eng, tp, dp, cfgT.vocab_size, max_new, device)
        if not np.array_equal(served_b, served_prompts):
            raise AssertionError("the two paths served different prompts")
        check_tie_rule("TP2+TP2", rows_b, oracle)
        log("multi-chip B: pass")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-3 on one chip; 4: the multi-chip phase only")
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.serve import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
                         f"({dev.device_kind}, {len(devices)} device(s))")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
                         f"found {len(devices)}")
    enable_compile_cache()
    device = f"{dev.device_kind} ({dev.platform})"
    log(f"device: {device}, {len(devices)} chip(s); jax {jax.__version__}")

    if args.chips == 1:
        one_chip(args.max_new, device)
    else:
        four_chips(args.max_new, device)
    for d in devices[: args.chips]:
        stats = d.memory_stats() or {}
        log(f"peak_bytes_in_use {d.id}: {stats.get('peak_bytes_in_use')} "
            f"of {stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                              "count": len(devices)}}))


if __name__ == "__main__":
    main()
