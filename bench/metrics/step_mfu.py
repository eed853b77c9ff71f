"""The whole step's share of the chip's bf16 peak while the device is
busy: the model FLOPs of the traced steps (``bench/flops.py``) over the
busy time of each chip times the peak, averaged over chips.  It bounds
every kernel's roofline claim: a kernel taken off the path leaves its own
roofline silent, but not this."""
from bench.trace import busy_ns


def read(trace, ctx):
    busy = sum(busy_ns(trace, c) for c in trace.chips) / len(trace.chips)
    flops = ctx["model_flops_per_chip_step"] * ctx["steps"]
    return 100.0 * flops / (busy / 1e9 * ctx["bf16_flops"])
