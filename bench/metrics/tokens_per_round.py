"""Round: tokens emitted per occupied row per round (the program's request
records: emitted tokens over rounds, summed over requests).  Moves ``tok_s``."""


def read(run):
    recs = list(run.server.records.values())
    rounds = sum(r.n_rounds for r in recs)
    return sum(r.n_tokens for r in recs) / rounds if rounds else None
