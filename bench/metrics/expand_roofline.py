"""Kernels: share of its roofline that one draft tree expansion reaches: the
least time for the call (``bench/roofline.py``: the draft's weights, the K/V
rows of prefix and tree, ``w`` leaves per row) over the measured device time
per ``jit_expand`` call.  Moves ``tok_s``."""

from bench.roofline import least_time


def read(run):
    if not run.trace or not run.peak:
        return None
    p = run.trace["draft_programs"].get("jit_expand")
    if not p or not p["calls"]:
        return None
    prog = run.cell.config["program"]
    flops, nbytes = run.draft.call(int(run.cell.mix["slots"]), prog["w"],
                                   run.mean_plen + prog["bs"], tp=max(run.n_draft, 1))
    t, _ = least_time(flops, nbytes, run.peak)
    return 100.0 * t / (p["s"] / p["calls"])
