"""Device time under the program's JAX name scopes, from a reduced trace.

An op's name stack (``Op.stack``) is the ``/``-separated path of scopes and
transforms it was traced under, e.g.
``jit(step_fn)/transpose(jvp(model))/while/body/attention/...``.  A scope is
matched as a whole segment, never as a substring of a longer name; a
segment's scope is its name inside any transform wrappers, so ``model`` is
the scope of ``jvp(model)`` and of ``transpose(jvp(model))``.  The program
opens ``model`` around the loss (``train/trainer.py::_loss_and_grads``,
``core/overlap.py::overlapped_loss_and_grads``), ``attention`` around the
q-chunked attention core (``models/attention.py::attn_train``) and
``optimizer`` around the norm, clip and update
(``train/trainer.py::_build_phase_step``).
"""
from __future__ import annotations

import re

# the per-bucket sync scopes, whose ops ``sync_ms`` reads
from bench.metrics.sync_ms import SCOPES as BUCKET_SCOPES  # noqa: F401

FORWARD = "jvp(model)"
BACKWARD = "transpose(jvp(model))"
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()*([^()]*?)\)*$")


def scope(segment: str) -> str:
    """The scope a stack segment names, without transform wrappers."""
    m = _WRAPPED.match(segment)
    return m.group(1) if m else segment


def ms_per_step(trace, ctx, keep) -> float | None:
    """Self time a step, averaged over chips, of the window's ops whose
    stack segments ``keep`` accepts; ``None`` where no op is accepted, so
    that a program without the scope leaves the metric out."""
    lo, hi = trace.window
    total, found = 0, False
    for ops in trace.chips.values():
        for op in ops:
            if lo <= op.start < hi and op.stack and keep(op.stack.split("/")):
                total += op.self_ns
                found = True
    if not found:
        return None
    return total / len(trace.chips) / ctx["steps"] / 1e6


def under(name: str):
    """A ``keep`` that accepts stacks with a segment of scope ``name``."""
    return lambda segs: any(scope(s) == name for s in segs)
