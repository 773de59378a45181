"""Device: share of the traced window in which no operation ran on the
device that runs verify (profiler trace).  Moves ``tok_s``."""


def read(run):
    if not run.trace or run.trace.get("idle_share") is None:
        return None
    return 100.0 * run.trace["idle_share"]
