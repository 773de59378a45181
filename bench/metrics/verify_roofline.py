"""Kernels: share of its roofline that one verify call reaches: the least
time the chip could take for the call (``bench/roofline.py``: the target's
weights, the K/V prefix rows and the tree tokens; peaks from
``bench/peaks.json``) over the measured device time per ``jit_verify`` call.
Moves ``tok_s``."""

from bench.roofline import least_time


def read(run):
    if not run.trace or not run.peak:
        return None
    p = run.trace["programs"].get("jit_verify")
    if not p or not p["calls"]:
        return None
    prog = run.cell.config["program"]
    flops, nbytes = run.target.call(int(run.cell.mix["slots"]), prog["bs"], run.mean_plen,
                                    tp=run.n_target)
    t, _ = least_time(flops, nbytes, run.peak)
    return 100.0 * t / (p["s"] / p["calls"])
