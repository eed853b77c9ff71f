"""Share of its roofline that the fused error-feedback kernel
(``kernels/ef_covap.ef_update``) reaches, over every call in the window.

A call streams two f32 inputs and writes two f32 outputs of one length
(``flops.ef_update_bytes``).  Every step the calls cover every gradient
element once, so the elements the work needs are ``flops.param_elements``
a step, from the configuration's shapes; the calls themselves run on
``(rows, 128)`` views padded to whole blocks, and that padding is not
counted.  The bound is HBM bandwidth: the least time of the calls is their
bytes held in HBM over the chip's HBM peak.  XLA may place an operand in
the on-chip VMEM, where its traffic costs no HBM bandwidth; the trace shows
that placement as memory space ``S(1)`` in the operand's layout.  The share
of the four arrays held in HBM is read per call and weighted by the call's
padded length.
"""
import re

from bench.flops import EF_UPDATE_OPERANDS, ef_update_bytes

KERNEL = re.compile(r"^%?ef_update(\.\d+)? = ")
ARRAY = re.compile(r"\b[a-z]+\d+\[([\d,]*)\]\{([^}]*)\}")


def hbm_share(text: str) -> tuple[int, float]:
    """(padded elements per operand, share of the four arrays held in HBM)."""
    head = text.split(", custom_call_target")[0]
    arrays = [(dims, layout) for dims, layout in ARRAY.findall(head)
              if dims.count(",") >= 1]
    if len(arrays) != EF_UPDATE_OPERANDS:
        raise ValueError(f"ef_update call with {len(arrays)} array operands "
                         f"and results: {head[:300]}")
    n = 1
    for d in arrays[0][0].split(","):
        n *= int(d)
    in_hbm = sum(not re.search(r"S\([1-9]\)", lay) for _, lay in arrays)
    return n, in_hbm / EF_UPDATE_OPERANDS


def read(trace, ctx):
    lo, hi = trace.window
    padded, in_hbm, time_ns = 0, 0.0, 0
    for ops in trace.chips.values():
        for op in ops:
            if lo <= op.start < hi and KERNEL.match(op.text):
                n, share = hbm_share(op.text)
                padded += n
                in_hbm += n * share
                time_ns += op.self_ns
    if not time_ns:
        return None
    needed = ctx["ef_elements_per_step"] * ctx["steps"] * len(trace.chips)
    if not 0.9 * padded <= needed <= padded:
        raise ValueError(f"ef_update calls cover {padded} padded elements in "
                         f"the window, the shapes need {needed}")
    bound_s = ef_update_bytes(needed) * (in_hbm / padded) / ctx["hbm_bytes_per_s"]
    return 100.0 * bound_s / (time_ns / 1e9)
