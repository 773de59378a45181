"""Stepper: host milliseconds per round blocked in the round's one
designated sync, the ``jax.device_get`` under the ``sync_emitted`` span
(``SpecStats.sync_s`` over the rounds): how long the host waits for the
device.  None for a program without the counter.  Moves ``tok_s``."""


def read(run):
    sync_s = getattr(run.spec, "sync_s", None)
    if sync_s is None or not run.server.rounds:
        return None
    return 1e3 * sync_s / run.server.rounds
