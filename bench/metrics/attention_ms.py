"""Device time per step of the attention core, forward and backward: the
self time of the ops under the ``attention`` scope of
``models/attention.py::attn_train`` (on the chip, for full causal
multi-head layers, the fused kernels of ``kernels/flash_attention.py`` and
the backward's row sums; elsewhere the q-chunked scores, softmax and
value product), without the QKV and output projections, averaged over
chips."""
from bench.scopes import ms_per_step, under

keep = under("attention")


def read(trace, ctx):
    return ms_per_step(trace, ctx, keep)
