"""Device time per step of the model's forward pass: the self time of the
ops whose name stack holds the segment ``jvp(model)`` and no transpose,
averaged over chips.  The remat recompute runs inside the backward pass
and is read by ``backward_ms``."""
from bench.scopes import FORWARD, ms_per_step


def keep(segs) -> bool:
    return FORWARD in segs and not any(s.startswith("transpose(")
                                       for s in segs)


def read(trace, ctx):
    return ms_per_step(trace, ctx, keep)
