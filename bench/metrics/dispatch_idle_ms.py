"""Device idle a step, in ms, while the host was in the trainer's dispatch
of a step or waited for its batch: the idle gaps of ``device_idle_pct``,
mean over chips, in the stretches where the innermost of the trainer's host
spans (``train_step``, ``train.*``) is ``train.dispatch`` or
``train.batch_wait``.  Nothing to read in a trace without those spans."""
from bench.trace import covered_ns, innermost, subtract, union

WAITS = ("train.dispatch", "train.batch_wait")


def read(trace, ctx):
    if not any(n in WAITS for n, _, _ in trace.spans):
        return None
    lo, hi = trace.window
    waits = union(innermost(trace.spans, WAITS), lo, hi)
    idle = [covered_ns(subtract(waits, ((o.start, o.end) for o in ops)))
            for ops in trace.chips.values()]
    return sum(idle) / len(idle) / ctx["steps"] / 1e6
