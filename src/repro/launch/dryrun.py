import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, prove memory fit, and extract roofline terms.

MUST be run as its own process (the two lines above pin 512 placeholder host
devices before jax initializes — never set that globally).

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b --shape decode_32k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1

Results land in benchmarks/results/dryrun/<mesh>/<arch>__<shape>.json and
feed EXPERIMENTS.md §Dry-run / §Roofline.
"""

import argparse
import json
import traceback

import jax

from repro.configs import ASSIGNED, SHAPES, cell_applicable, get_config
from repro.flags import override_flags
from repro.launch.hlo_parse import analyze
from repro.launch.hlo_stats import model_flops_per_chip, roofline_terms_from_module
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import cell_specs, dryrun_config
from repro.obs.clock import monotonic
from repro.sharding import use_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmarks", "results", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool, flag_overrides: dict | None = None):
    """Lower + compile one cell; returns the result record (raises on failure)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    shape = SHAPES[shape_name]
    cfg_pub = get_config(arch)
    ok, why = cell_applicable(cfg_pub, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": "pod2" if multi_pod else "pod1",
                "status": "skipped", "reason": why}

    flags = dict(
        scan_layers=True,
        remat="full" if shape.kind == "train" else "none",
        seq_shard_acts=shape.kind in ("train", "prefill"),
    )
    flags.update(flag_overrides or {})

    # monotonic, not time.time(): an NTP step mid-compile used to be able to
    # produce negative lower/compile durations in the dry-run records
    t0 = monotonic()
    with use_mesh(mesh), override_flags(**flags):
        step, args, meta = cell_specs(arch, shape_name, mesh)
        donate = {"train": (0, 1), "prefill": (), "decode": (1,)}[shape.kind]
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = monotonic() - t0
        compiled = lowered.compile()
        t_compile = monotonic() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    mc = analyze(compiled.as_text())  # loop-aware, trip-scaled accounting
    cfg = dryrun_config(arch, mesh)
    rf = roofline_terms_from_module(mc, model_flops_per_chip(cfg, shape, n_chips))

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod2" if multi_pod else "pod1",
        "status": "ok",
        "n_chips": int(n_chips),
        "flags": flags,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
            ),
        },
        "collectives": {
            "bytes_by_kind": mc.collective,
            "bytes_by_kind_raw": mc.collective_raw,
            "count_by_kind": mc.collective_count,
        },
        "loop_trips": mc.loop_trips,
        "cost_analysis_raw": {  # XLA aggregate (loop bodies counted once)
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "roofline": rf.as_dict(),
    }
    return rec


def save(rec: dict, out_dir: str):
    d = os.path.join(out_dir, rec["mesh"])
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{rec['arch']}__{rec['shape']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def summarize(rec: dict) -> str:
    if rec["status"] != "ok":
        return f"{rec['arch']:22s} {rec['shape']:12s} {rec['mesh']}: SKIP ({rec['reason'][:60]})"
    r = rec["roofline"]
    gib = rec["memory"]["peak_bytes_per_device"] / 2**30
    return (
        f"{rec['arch']:22s} {rec['shape']:12s} {rec['mesh']}: ok "
        f"compile={rec['compile_s']:.0f}s mem/dev={gib:.2f}GiB "
        f"t_comp={r['t_compute_s']:.2e} t_mem={r['t_memory_s']:.2e} "
        f"t_coll={r['t_collective_s']:.2e} -> {r['bottleneck']}"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ASSIGNED + ["all"], default="all")
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default="all")
    ap.add_argument("--mesh", choices=["pod1", "pod2", "both"], default="both")
    ap.add_argument("--all", action="store_true", help="every arch x shape x mesh")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--flag", action="append", default=[],
                    help="flags override k=v (e.g. seq_shard_acts=False)")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or args.arch == "all") else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape == "all") else [args.shape]
    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]
    overrides = {}
    for kv in args.flag:
        k, v = kv.split("=", 1)
        overrides[k] = {"True": True, "False": False}.get(v, v if not v.isdigit() else int(v))

    failures = []
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                mesh_name = "pod2" if multi_pod else "pod1"
                path = os.path.join(args.out, mesh_name, f"{arch}__{shape}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"{arch:22s} {shape:12s} {mesh_name}: cached")
                    continue
                try:
                    rec = run_cell(arch, shape, multi_pod, overrides)
                except Exception as e:  # noqa: BLE001 — report, continue
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "fail", "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    failures.append((arch, shape, mesh_name))
                save(rec, args.out)
                print(summarize(rec) if rec["status"] != "fail"
                      else f"{arch:22s} {shape:12s} {mesh_name}: FAIL {rec['error'][:100]}",
                      flush=True)

    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nall cells ok")


if __name__ == "__main__":
    main()
