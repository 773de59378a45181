"""A whole harness run on the disaggregated split, the target on two chips
at TP=2 and the draft on two more, at smoke shapes on four virtual CPU
devices (the look for a chip skipped): a sound run is correct, and one with
the exchange between the chip groups left out is not.  The fault: the
target verifies the plan it already holds instead of the one the draft
group sends it.

Four devices need ``XLA_FLAGS`` before JAX starts, so the run is a child
process."""

import json
import os
import subprocess
import sys
import textwrap

from bench.tests.smoke import BENCH

SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path[:0] = [sys.argv[2], sys.argv[2] + "/src"]
    from bench import run as R
    from bench.tests import smoke
    from repro.core import engine as E

    program = dict(smoke.SMOKE_CONFIG["program"], n_target=2, n_draft=2)
    smoke.SMOKE_CONFIG.update(chips=4, program=program)
    root = smoke.make_root(Path(sys.argv[1]))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        w["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = {"sound": R.run(root, "smoke.single", 2**31 + 77, 1.5, False, require_tpu=False)}

    dispatch = E.EngineSession._dispatch
    held = {}

    def without_exchange(self, plan, tcache):
        plan = held.setdefault(id(self), plan)  # the first plan the target got
        return dispatch(self, plan, tcache)

    E.EngineSession._dispatch = without_exchange
    out["fault"] = R.run(root, "smoke.single", 2**31 + 77, 1.5, False, require_tpu=False)
    print(json.dumps({k: {"correct": v["correct"], "check": v["check"],
                          "count": v["device"]["count"]} for k, v in out.items()}))
""")


def test_exchange_between_chip_groups_left_out_fails(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path), str(BENCH.parent)],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["count"] == 4
    assert out["sound"]["correct"]
    assert out["sound"]["check"]["max_gap"] <= out["sound"]["check"]["max_gap_limit"]
    assert not out["fault"]["correct"]
