"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips: 1 - (union of the chip's op intervals) / window."""
from bench.trace import busy_ns


def read(trace, ctx):
    busy = [busy_ns(trace, chip) for chip in trace.chips]
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace.window_ns)
