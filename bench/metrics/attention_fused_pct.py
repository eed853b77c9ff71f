"""Share of the attention core's device time spent in the fused attention
kernels: the self time of the ops under the ``attention_kernel`` scope
nested in ``attention`` (the Pallas forward, dK/dV and dQ calls of
``kernels/flash_attention.py``), over the self time of all ops under
``attention`` (``models/attention.py::attn_train``, both passes; the rest is
the layout moves and the backward's row sums).  ``None`` where no op lies
under ``attention_kernel``, as on a program that runs the q-chunked scan."""
from bench.scopes import ms_per_step, under

attention = under("attention")
kernel = under("attention_kernel")


def read(trace, ctx):
    fused = ms_per_step(trace, ctx, lambda segs: attention(segs)
                        and kernel(segs))
    if fused is None:
        return None
    return 100.0 * fused / ms_per_step(trace, ctx, attention)
