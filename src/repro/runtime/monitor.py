"""Online CCR monitor: the measurement half of the adaptive runtime.

The planner picks ``I = ceil(CCR)`` from the *analytic* profiler before a
single step runs (``core.ccr.analytic_ccr``).  The paper's headline claim,
however, is *adaptive* compression — the interval must track the CCR the
hardware actually delivers, which drifts with stragglers, congested links
and evolving batch shapes.  This module closes the measurement side of
that loop (DESIGN.md §10):

* :class:`CCRMonitor` — a per-step ring buffer of wall times plus a
  per-phase ring buffer of comm/compute decompositions, yielding a
  *running measured CCR* (overall and per phase);
* :class:`PhaseProbe` — produces one decomposition sample by timing the
  **compute-only** sub-program (the same step math with every collective
  elided — ``build_train_step(mesh=None)``) and the **schedule-only**
  sub-program (exactly the phase's planned collectives on zero buffers)
  against the full phase executable, via ``core.ccr.measure_ccr``.

The probe is deliberately a plain callable ``(state, batch, phase) ->
PhaseSample`` so tests and benchmarks can inject synthetic comm slowdowns
without ever touching a clock.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.ccr import measure_ccr


@dataclasses.dataclass(frozen=True)
class PhaseSample:
    """One measured comm/compute decomposition of a phase's step."""

    phase: int
    t_comp: float
    t_comm: float
    step: int = 0
    # wall time of the full step (collectives included); 0.0 on synthetic
    # probes.  t_comp + t_comm - t_full is the communication the overlap
    # engine actually hid this sample (perfmodel.achieved_overlap_fraction).
    t_full: float = 0.0

    @property
    def ccr(self) -> float:
        return self.t_comm / max(self.t_comp, 1e-12)

    @property
    def achieved_overlap(self) -> float | None:
        """Measured overlap fraction, or None when the probe recorded no
        full-step wall time (synthetic probes)."""
        if self.t_full <= 0.0:
            return None
        from repro.core.perfmodel import achieved_overlap_fraction

        return achieved_overlap_fraction(self.t_comp, self.t_comm, self.t_full)


class CCRMonitor:
    """Ring buffers of measured step times and CCR decompositions.

    ``record_step`` feeds the cheap always-on signal (full-step wall time,
    one entry per training step); ``record_sample`` feeds the expensive
    occasional signal (a :class:`PhaseSample` from a probe).  The running
    measured CCR is the mean over the most recent ``window`` samples —
    per phase when asked, pooled otherwise.
    """

    def __init__(self, window: int = 32):
        self.window = int(window)
        self._steps: collections.deque = collections.deque(maxlen=self.window)
        self._samples: collections.deque = collections.deque(maxlen=self.window)

    # ---- feeding ----------------------------------------------------------
    def record_step(self, step: int, phase: int, wall_s: float) -> None:
        self._steps.append((int(step), int(phase), float(wall_s)))

    def record_sample(self, sample: PhaseSample) -> None:
        self._samples.append(sample)

    def clear_samples(self) -> None:
        """Drop the decomposition window (measurements taken under a plan
        that no longer exists must not drive the next decision)."""
        self._samples.clear()

    # ---- reading ----------------------------------------------------------
    @property
    def num_samples(self) -> int:
        return len(self._samples)

    def samples(self, phase: int | None = None) -> list[PhaseSample]:
        if phase is None:
            return list(self._samples)
        return [s for s in self._samples if s.phase == phase]

    def mean_step_time(self, phase: int | None = None) -> float | None:
        ts = [w for (_, p, w) in self._steps if phase is None or p == phase]
        return sum(ts) / len(ts) if ts else None

    def measured_times(self, phase: int | None = None) -> dict | None:
        """Mean ``(t_comp, t_comm)`` over the sample window, or None when
        no probe has run yet.  Samples with a full-step wall time also
        yield ``achieved_overlap`` — the fraction of the wire time the
        executed step actually hid under compute (predicted-vs-achieved
        counterpart of ``perfmodel.overlap_fraction``)."""
        ss = self.samples(phase)
        if not ss:
            return None
        t_comp = sum(s.t_comp for s in ss) / len(ss)
        t_comm = sum(s.t_comm for s in ss) / len(ss)
        out = {"t_comp": t_comp, "t_comm": t_comm,
               "ccr": t_comm / max(t_comp, 1e-12), "n": len(ss)}
        timed = [s for s in ss if s.t_full > 0.0]
        if timed:
            from repro.core.perfmodel import achieved_overlap_fraction

            out["t_full"] = sum(s.t_full for s in timed) / len(timed)
            out["achieved_overlap"] = achieved_overlap_fraction(
                sum(s.t_comp for s in timed) / len(timed),
                sum(s.t_comm for s in timed) / len(timed),
                out["t_full"],
            )
        return out

    def measured_ccr(self, phase: int | None = None) -> float | None:
        mt = self.measured_times(phase)
        return None if mt is None else mt["ccr"]

    def summary(self) -> dict:
        """JSON-serialisable digest for logs / FitResult."""
        mt = self.measured_times()
        return {
            "steps_recorded": len(self._steps),
            "probe_samples": len(self._samples),
            "mean_step_s": self.mean_step_time(),
            "measured_ccr": None if mt is None else mt["ccr"],
            "t_comp": None if mt is None else mt["t_comp"],
            "t_comm": None if mt is None else mt["t_comm"],
            "achieved_overlap": (
                None if mt is None else mt.get("achieved_overlap")
            ),
        }


# ---------------------------------------------------------------------------
# the real probe: sub-program timing against the live trainer
# ---------------------------------------------------------------------------

def _blocked(fn: Callable, *args) -> Callable[[], None]:
    def run():
        jax.block_until_ready(fn(*args))

    return run


def _chained(step: Callable, params, opt, comp, batch,
             step_no) -> Callable[[], None]:
    """Time the trainer's own (donating) phase executable: each call
    consumes the state the call before it returned, starting from a
    private copy, so the caller's live state is never donated."""
    carry = jax.block_until_ready(jax.tree.map(jnp.copy, (params, opt, comp)))

    def run():
        nonlocal carry
        *carry, metrics = step(*carry, batch, step_no)
        jax.block_until_ready((carry, metrics))

    return run


class PhaseProbe:
    """Measures one phase's comm/compute decomposition on live state.

    Three sub-programs, cached after first build:

    * **full** — the trainer's own phase executable (collectives
      included), chained on a copy of the state since it donates its input;
    * **compute-only** — the identical step built with ``mesh=None`` so
      every collective is elided (``core.comm`` reduces become identities);
    * **schedule-only** — the **dense** schedule's collectives replayed on
      zero buffers (every bucket, uncompressed wire).

    ``core.ccr.measure_ccr`` does the timing.  The comm term is the dense
    one deliberately: the paper's rule ``I = ceil(CCR)`` is defined on the
    *uncompressed* comm/compute balance.  Timing the live compressed
    executable's collectives instead would divide the measured comm by
    ~I — the controller would then see CCR ≈ dense/I, conclude ``I = 1``,
    re-plan, see the dense CCR again, and oscillate.  Measuring the dense
    schedule keeps the measured CCR a property of the *workload*, so the
    controller has a fixed point.
    """

    def __init__(self, trainer, *, warmup: int = 1, iters: int = 2):
        self.trainer = trainer
        self.warmup = int(warmup)
        self.iters = int(iters)
        self._compute_only: dict[int, Callable] = {}
        self._comm_only: dict[int, Callable] = {}

    def invalidate(self) -> None:
        """Drop cached sub-programs (after a re-plan)."""
        self._compute_only.clear()
        self._comm_only.clear()

    # ---- sub-program builders ---------------------------------------------
    def _compute_fn(self, phase: int) -> Callable:
        if phase not in self._compute_only:
            from repro.train.trainer import build_train_step

            tr = self.trainer
            self._compute_only[phase] = build_train_step(
                tr.model, tr.optimizer, tr.compressor, tr.plan,
                phase=phase, mesh=None, dp_axes=(),
                clip_norm=tr.tc.clip_norm, donate=False,
            )
        return self._compute_only[phase]

    def _comm_fn(self, phase: int) -> Callable:
        # keyed on 0: the dense schedule is phase-independent
        if 0 not in self._comm_only:
            from repro.core import get_compressor

            tr = self.trainer
            dense = get_compressor("none").plan_phase(
                tr.plan, 0, world=tr.dp_world
            )
            self._comm_only[0] = build_schedule_only_fn(
                dense, mesh=tr.mesh, dp_axes=tr.dp_axes
            )
        return self._comm_only[0]

    # ---- the probe call ---------------------------------------------------
    def __call__(self, state, batch, phase: int) -> PhaseSample:
        tr = self.trainer
        full = tr._phase_fn(phase)
        step = jnp.asarray(state["step"], jnp.int32)
        args = (state["params"], state["opt"], state["comp"], batch, step)
        if tr.hierarchical:
            # the compute-only program is per-pod: take pod 0's block of
            # the full (n_pods, ...) host-side state
            from repro.train.trainer import strip_pod_block

            flat = strip_pod_block(
                (args[0], args[1], args[2]), expect_local=False
            )
            comp_args = flat + (batch, step)
        else:
            comp_args = args
        res = measure_ccr(
            _chained(full, *args),
            _blocked(self._compute_fn(phase), *comp_args),
            step_comm_only=_blocked(self._comm_fn(phase)),
            warmup=self.warmup,
            iters=self.iters,
        )
        return PhaseSample(
            phase=int(phase),
            t_comp=res["t_comp"],
            t_comm=res["t_comm"],
            step=int(state["step"]),
            t_full=res["t_full"],
        )


def build_schedule_only_fn(schedule, *, mesh=None, dp_axes: Sequence[str] = ()):
    """jit a program that performs exactly the collectives a
    ``CommSchedule`` plans — on zero buffers, one per planned call — so the
    wire cost of a phase can be timed in isolation.

    Single-process (``mesh=None``): the collectives are identities, so the
    measured time is the (near-zero) dispatch floor — the honest answer on
    one worker.
    """
    import numpy as np

    shapes = [
        (max(1, c.payload_bytes // max(np.dtype("float32").itemsize, 1)),)
        for c in schedule.calls
    ]

    def body(*bufs):
        from jax import lax

        out = []
        for b in bufs:
            if mesh is not None and dp_axes:
                out.append(lax.psum(b, tuple(dp_axes)))
            else:
                out.append(b + 0.0)
        return tuple(out)

    if mesh is not None and dp_axes:
        from jax.sharding import PartitionSpec as P

        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=tuple(P() for _ in shapes),
            out_specs=tuple(P() for _ in shapes),
            axis_names=set(dp_axes), check_vma=False,
        )
        jitted = jax.jit(mapped)
    else:
        jitted = jax.jit(body)

    bufs = tuple(jnp.zeros(s, jnp.float32) for s in shapes)

    def run():
        if bufs:
            jax.block_until_ready(jitted(*bufs))

    return run


# ---------------------------------------------------------------------------
# synthetic probes (tests / benchmarks) and one-off workload measurement
# ---------------------------------------------------------------------------

def synthetic_probe(
    t_comp: float, ccr: float | Callable[[int], float]
) -> Callable:
    """A probe that reports a prescribed CCR instead of touching a clock —
    the injected-comm-slowdown harness of the acceptance tests.  ``ccr``
    may be a float or a ``step -> ccr`` callable (drifting links)."""

    def probe(state, batch, phase) -> PhaseSample:
        step = int(state["step"]) if isinstance(state, dict) else 0
        c = ccr(step) if callable(ccr) else float(ccr)
        return PhaseSample(
            phase=int(phase), t_comp=float(t_comp),
            t_comm=float(t_comp) * c, step=step,
        )

    return probe


def measure_workload_ccr(
    trainer, state, batch, *, phases: Sequence[int] | None = None,
    warmup: int = 1, iters: int = 2,
) -> dict:
    """One-off measured CCR of a trainer's workload: probes each requested
    phase once and pools the decompositions.  This is what
    ``repro.api.tune(measured=True)`` reports alongside the analytic
    ranking."""
    probe = PhaseProbe(trainer, warmup=warmup, iters=iters)
    todo = list(phases) if phases is not None else list(range(trainer.num_phases))
    mon = CCRMonitor(window=max(len(todo), 8))
    for p in todo:
        st = dict(state)
        mon.record_sample(probe(st, batch, int(p)))
    out = mon.measured_times() or {"t_comp": 0.0, "t_comm": 0.0, "ccr": 0.0}
    out["per_phase"] = {
        s.phase: s.ccr for s in mon.samples()
    }
    return out


__all__ = [
    "CCRMonitor",
    "PhaseProbe",
    "PhaseSample",
    "build_schedule_only_fn",
    "measure_workload_ccr",
    "synthetic_probe",
]
