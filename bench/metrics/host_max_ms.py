"""Stepper: the longest single round's host milliseconds outside its sync
(``ServerStats.round_max_s``): a stall of the host in one round shows here
though the mean hides it.  None for a program without the counter.  Moves
``tok_s``."""


def read(run):
    v = getattr(run.server, "round_max_s", None)
    if v is None or not run.server.rounds:
        return None
    return 1e3 * v
