"""Round: share of async rounds whose lookahead tree was adopted
(``SpecStats.spec_commits / spec_rounds``); the rest roll back and re-root.
Moves ``tok_s``."""


def read(run):
    s = run.spec
    return 100.0 * s.spec_commits / s.spec_rounds if s.spec_rounds else None
