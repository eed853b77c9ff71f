"""Faults planted underneath the timed path, to show that ``correct`` sees
them.  The benchmark's own runs never use this module; the CPU tests and
``bench/calibrate.py`` do.

* ``unchanged``: every step returns the state it was given.
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
    if fault in ("no_exchange", "no_feedback"):
        owner, attr, broken = {
            "no_exchange": (stages, "pmean", lambda x, axis_names: x),
            "no_feedback": (error_feedback.EFSchedule, "coefficient",
                            lambda self, step: jnp.float32(0.0)),
        }[fault]
        saved = getattr(owner, attr)
        setattr(owner, attr, broken)
        try:
            yield
        finally:
            setattr(owner, attr, saved)
        return

    original = trainer.Trainer._phase_fn

    def phase_fn(self, phase):
        fn = original(self, phase)

        def step(params, opt, comp, batch, step_no):
            if fault == "half_batch":
                half = batch["tokens"].shape[0] // 2
                batch = {k: v[:half] for k, v in batch.items()}
            out = fn(params, opt, comp, batch, step_no)
            if fault == "unchanged":
                return (params, opt, comp, out[3])
            return out

        step._cache_size = fn._cache_size
        return step

    trainer.Trainer._phase_fn = phase_fn
    try:
        yield
    finally:
        trainer.Trainer._phase_fn = original
