"""Stepper: host milliseconds per round outside the round's one sync: the
program's round time (``ServerStats.round_s``, from the start of
``EngineStepper.step`` to the end of ``absorb_round``, the ``round`` span's
interval) less the seconds blocked in the sync (``SpecStats.sync_s``), over
the rounds.  It holds the dispatch of verify and the lookahead, the verdict
and any roll-back dispatch, absorb, stream and retire.  None for a program
without these counters.  Moves ``tok_s``."""


def read(run):
    round_s = getattr(run.server, "round_s", None)
    sync_s = getattr(run.spec, "sync_s", None)
    if round_s is None or sync_s is None or not run.server.rounds:
        return None
    return 1e3 * (round_s - sync_s) / run.server.rounds
