"""Router / queue: 90th percentile of the wait from arrival to admission
into a slot (``admitted_s - arrival_s`` of the program's request records),
over the requests due in the window.  Moves ``ttft_p90_ms``."""

from bench.run import quantile


def read(run):
    waits = [r.admitted_s - r.arrival_s for rid, r in run.server.records.items()
             if rid in run.reqs and run.reqs[rid].due_s < run.seconds]
    v = quantile(waits, 0.9)
    return None if v is None else 1e3 * v
