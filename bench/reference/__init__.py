"""Plain float32 references, one module per architecture, named by the
configuration file's ``reference`` key (the draft's by ``draft.reference``)
and found by path under the benchmark root (``spec.load_reference``).  They
import nothing of the program.  Each supplies ``hidden(weights, cfg, tokens,
control=False)`` and ``head(h, weights, cfg, nxt, control=False)``."""
