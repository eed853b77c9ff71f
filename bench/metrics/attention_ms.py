"""Device time per step of the attention core, forward and backward: the
self time of the ops under the ``attention`` scope (the q-chunked scores,
softmax and value product of ``models/attention.py::attn_train``, without
the QKV and output projections), averaged over chips."""
from bench.scopes import ms_per_step, under

keep = under("attention")


def read(trace, ctx):
    return ms_per_step(trace, ctx, keep)
