"""Benchmark harness: one entry per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table1 fig9

CSV outputs land in benchmarks/results/.  Regimes (measured vs derived) are
documented per module; the dry-run roofline table (EXPERIMENTS.md §Roofline)
is produced separately by repro.launch.dryrun.
"""

from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (
    allocation,
    kernel_micro,
    serving,
    tp_scaling,
)

BENCHES = {
    "table1": ("Paper Table 1  — TP scaling per model size", tp_scaling.run),
    "table7": ("Paper Tables 3/7 — kernel micro-benchmarks", kernel_micro.run),
    "fig9": ("Paper Figure 9 — draft/target allocation sweep", allocation.run),
    "serving": ("Serving — replicas x offered-load sweep (sharded runtime)", serving.run),
}


def main() -> None:
    names = sys.argv[1:] or list(BENCHES)
    failures = []
    for name in names:
        title, fn = BENCHES[name]
        print(f"\n=== {name}: {title} ===", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"  [{name} done in {time.time()-t0:.1f}s]")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED: {failures}")
        raise SystemExit(1)
    print("\nall benchmarks ok")


if __name__ == "__main__":
    main()
