"""Model programs, draft side: device milliseconds per round of the draft's
programs (tree expansion, prefix fill, KV moves, re-root, accept prediction,
plan selection), from the profiler trace, on the device that runs them (on
one chip the target's).  The KV moves are ``jit(functools.partial(kv_move))``
in the program and reach the trace as ``jit__unknown``, the only unnamed
program of a round.  Moves ``tok_s``."""

DRAFT = ("jit_expand", "jit_fill_prefix", "jit__unknown", "jit_reroot", "jit_predict_accept",
         "jit_select_plan")


def read(run):
    if not run.trace:
        return None
    calls = run.trace["programs"].get("jit_verify", {}).get("calls", 0)
    if not calls:
        return None
    progs = run.trace["draft_programs"]
    return 1e3 * sum(progs[p]["s"] for p in DRAFT if p in progs) / calls
