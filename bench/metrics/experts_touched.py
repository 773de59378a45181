"""Model programs, target side: the routed experts that one verify call's
live tree tokens reach, per MoE layer (``SpecStats.experts_touched`` over
``SpecStats.moe_layer_calls``, counted inside the verify program and held
on the device until read).  A verify reads the weights of these experts
only, so its bytes grow with them.  None for a target with no MoE layer, or
a program without the counters.  Moves ``tok_s``."""


def read(run):
    calls = getattr(run.spec, "moe_layer_calls", 0)
    if not calls:
        return None
    return run.spec.experts_touched / calls
