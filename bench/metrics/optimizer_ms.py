"""Device time per step of the optimizer: the self time of the ops under
the ``optimizer`` scope (gradient norm, clipping, the AdamW update and its
application to the parameters), averaged over chips."""
from bench.scopes import ms_per_step, under

keep = under("optimizer")


def read(trace, ctx):
    return ms_per_step(trace, ctx, keep)
