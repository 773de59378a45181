"""Model programs, target side: device milliseconds per round of the
verify and compaction programs (``jit_verify``, ``jit_compact``), from the
profiler trace.  Moves ``tok_s``."""

TARGET = ("jit_verify", "jit_compact")


def read(run):
    if not run.trace:
        return None
    progs = run.trace["programs"]
    calls = progs.get("jit_verify", {}).get("calls", 0)
    if not calls:
        return None
    return 1e3 * sum(progs[p]["s"] for p in TARGET if p in progs) / calls
