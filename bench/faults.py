"""Faults planted underneath the timed path, to show that ``correct`` sees
them.  The benchmark's own runs never use this module; the CPU tests and
``bench/calibrate.py`` do.

* ``unchanged``: every step returns the state it was given.  The step's own
  graph returns its inputs, so a step that donates its state hands the same
  buffers back, and no copy of the state is held.
* ``half_batch``: every step sees the first half of its global batch only,
  so the mean is taken over the rest.
* ``no_exchange``: the cross-chip mean of each bucket is left out, so each
  chip applies its own gradient.
* ``no_feedback``: the error-feedback coefficient is 0, so the residual is
  kept but never added back to the gradient.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

FAULTS = ("unchanged", "half_batch", "no_exchange", "no_feedback")
SCHEDULES = ("comm_schedule", "prev_schedule", "pod_schedule")


def keeps_state(build):
    """The step builder ``build``, whose steps compute as before and return
    the params, optimizer and compressor state they were given."""
    def builder(*args, **kwargs):
        fn = build(*args, **kwargs)

        def step_fn(params, opt_state, comp_state, batch, step):
            out = fn(params, opt_state, comp_state, batch, step)
            return params, opt_state, comp_state, out[3]

        for name in SCHEDULES:
            setattr(step_fn, name, getattr(fn, name))
        return step_fn
    return builder


def halves_batch(phase_fn):
    """``Trainer._phase_fn``, whose steps see the first half of each batch."""
    def patched(self, phase):
        fn = phase_fn(self, phase)

        def step(params, opt, comp, batch, step_no):
            half = batch["tokens"].shape[0] // 2
            return fn(params, opt, comp,
                      {k: v[:half] for k, v in batch.items()}, step_no)

        step._cache_size = fn._cache_size
        return step
    return patched


@contextlib.contextmanager
def planted(fault: str | None):
    """Patch the program for the duration of the block (``None``: no fault)."""
    from repro.core import error_feedback, stages
    from repro.train import trainer

    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    patches = {
        "unchanged": [(trainer, name, keeps_state(getattr(trainer, name)))
                      for name in ("build_step_fn", "build_overlapped_step")],
        "half_batch": [(trainer.Trainer, "_phase_fn",
                        halves_batch(trainer.Trainer._phase_fn))],
        "no_exchange": [(stages, "pmean", lambda x, axis_names: x)],
        "no_feedback": [(error_feedback.EFSchedule, "coefficient",
                         lambda self, step: jnp.float32(0.0))],
    }[fault]
    saved = [(owner, attr, getattr(owner, attr)) for owner, attr, _ in patches]
    try:
        for owner, attr, broken in patches:
            setattr(owner, attr, broken)
        yield
    finally:
        for owner, attr, value in saved:
            setattr(owner, attr, value)
