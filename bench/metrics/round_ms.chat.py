"""Stepper: host milliseconds per global round of the serving loop, over
the time the loop had a request in flight (the run less the sleeps waiting
for arrivals) and the program's round count (``ServerStats.rounds``).  Moves
``tpot_p90_ms``."""


def read(run):
    rounds = run.server.rounds
    if not rounds:
        return None
    return 1e3 * (run.run_s - run.idle_s) / rounds
