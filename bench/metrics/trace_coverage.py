"""Device: the share of the program's verify dispatches that the device
trace holds: ``jit_verify`` calls in the traced window over the
``jit_verify`` dispatches the program counted in the same run
(``SpecStats.dispatches``).  100% means ``idle_share`` and ``breakdown``
saw every round; less means the trace lost events and reads the rounds it
lacks as idle.  An accelerator's trace has one event per program call; the
CPU backend's has one per operation, so there is nothing to read on it.
None for a program without the counter.  Moves ``tok_s``."""


def read(run):
    if not run.trace or not run.trace["device"].startswith("/device:"):
        return None
    n = getattr(run.spec, "dispatches", {}).get("jit_verify")
    if not n:
        return None
    return 100.0 * run.trace["programs"].get("jit_verify", {}).get("calls", 0) / n
