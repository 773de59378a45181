"""Device: the whole round's share of the chips' peak: the FLOPs that the
rounds' target verifications and draft expansions need
(``bench/roofline.py``, from shapes; ``SpecStats`` rounds and draft steps)
over the run's time, the chips and the bf16 peak (``bench/peaks.json``).
Moves ``tok_s``."""


def read(run):
    if not run.peak or not run.spec.rounds:
        return None
    prog = run.cell.config["program"]
    slots = int(run.cell.mix["slots"])
    fv, _ = run.target.call(slots, prog["bs"], run.mean_plen, tp=run.n_target)
    fe, _ = run.draft.call(slots, prog["w"], run.mean_plen + prog["bs"], tp=max(run.n_draft, 1))
    flops = run.spec.rounds * fv * run.n_target + run.spec.draft_steps * fe * max(run.n_draft, 1)
    return 100.0 * flops / (run.run_s * run.chips * run.peak["bf16_flops_per_s"])
