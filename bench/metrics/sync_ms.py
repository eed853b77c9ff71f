"""Device time per step of the COVAP sync pipeline: the self time of every
op whose JAX name stack holds one of the per-bucket scopes that
``core/stages.py`` opens (error feedback, the EF kernel and its padding
and slicing copies, and the bucket's collective), averaged over chips."""

SCOPES = ("covap_bucket_", "covap_arena_bucket_")


def read(trace, ctx):
    lo, hi = trace.window
    total, found = 0, False
    for ops in trace.chips.values():
        for op in ops:
            if lo <= op.start < hi and any(s in op.stack for s in SCOPES):
                total += op.self_ns
                found = True
    if not found:
        return None
    return total / len(trace.chips) / ctx["steps"] / 1e6
