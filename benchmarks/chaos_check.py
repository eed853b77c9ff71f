"""Resilience smoke gate (DESIGN.md §16): the guardrails must *work* and
be *cheap*.

Two checks:

1. **Chaos recovery** — ``repro.launch.chaos_gate`` in a subprocess (the
   fake 8-device count must be set before jax imports): a reduced covap
   run on an 8-worker CPU mesh under ``grad_nan`` + ``ef_blowup`` + a
   persistent ``grad_inf`` + a mid-run ``kill`` must heal through all
   three ladder rungs (skip-step / ef-flush / rewind), survive the
   kill via checkpoint restore + resume, end with a finite loss, and
   surface every trip/action/firing as schema-valid telemetry events
   matching the counters 1:1.
2. **Overhead** — a guarded step (``guards=True``: nonfinite + loss-spike
   + residual watchdog at their default cadences, no checkpointing) must
   cost within 3% of an unguarded one on the same precompiled trainer
   (interleaved min-of-trials, the kernel_bench/obs_check discipline).
   The µs column of the ``chaos/guard_overhead_frac`` row carries the
   dimensionless fraction (``frac/1e6`` — ``row()`` scales by 1e6);
   ``benchmarks.run`` lifts it into the ``guard_overhead_frac`` gauge of
   ``BENCH_<n>.json``.  Set ``REPRO_CHAOS_NO_OVERHEAD_GATE=1`` to record
   without gating on a hopelessly noisy box.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax

from .common import row

OVERHEAD_BUDGET = 1.03   # guarded step wall <= 3% over unguarded

_SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src")
)


def _chaos_gate() -> tuple[float, dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # fake CPU devices; the parent may hold a chip
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.chaos_gate"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    wall = time.perf_counter() - t0
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("CHAOS ")),
        "CHAOS <missing>",
    )
    if r.returncode != 0:
        raise AssertionError(
            f"chaos recovery gate failed: {line}\n{r.stderr[-2000:]}"
        )
    kv = dict(p.split("=", 1) for p in line.split()[1:])
    return wall, kv


def _overhead_gate(smoke: bool) -> tuple:
    """Interleaved min-of-trials guarded-vs-bare step wall on ONE
    precompiled trainer: both arms replay the identical step sequence
    from the same initial state, so the only delta is the guard work —
    the per-step host materialisation of ``total_loss``/``grad_norm``
    plus the cadenced residual-norm reduction."""
    from repro.configs import get_reduced
    from repro.data import DataConfig, make_loader
    from repro.models import build_model
    from repro.optim import sgd
    from repro.train.trainer import TrainConfig, Trainer

    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    model = build_model(cfg)
    tc = TrainConfig(compressor="covap", interval=2, log_every=1000,
                     steps=64)
    tr = Trainer(model, sgd(1e-3, momentum=0.9), tc)
    state = tr.init_state(jax.random.PRNGKey(0))
    loader = iter(make_loader(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=16, global_batch=4,
    )))

    steps = 8 if smoke else 12
    trials = 7 if smoke else 11
    tr.run(state, loader, steps=2, log=None)      # compile both phases
    tr.run(state, loader, steps=2, log=None, guards=True)   # + guard jits

    def timed(guards) -> float:
        t0 = time.perf_counter()
        out = tr.run(state, loader, steps=steps, log=None, guards=guards)
        # settle async dispatch: without this the bare arm measures only
        # the host loop, and the guarded arm's per-step sync looks like a
        # 200% "overhead" that is really the compute wall itself
        jax.block_until_ready(out["params"])
        return (time.perf_counter() - t0) / steps

    def measure() -> tuple:
        import gc

        gc.collect()    # don't let earlier modules' garbage bill a trial
        on, off = [], []
        for k in range(trials):
            # alternate pair order so a systematic second-position penalty
            # (frequency scaling, GC debt) is not charged to one arm
            if k % 2 == 0:
                off.append(timed(None))
                on.append(timed(True))
            else:
                on.append(timed(True))
                off.append(timed(None))
        min_on, min_off = min(on), min(off)
        return min_on / max(min_off, 1e-12) - 1.0, min_on, min_off

    # the ~3% budget sits below this box's trial-to-trial scheduler noise,
    # so re-measure up to 3 rounds and gate on the best: a structural
    # regression is over budget in EVERY round, a noise spike is not
    frac, min_on, min_off = measure()
    for _ in range(2):
        if frac <= OVERHEAD_BUDGET - 1.0:
            break
        frac, min_on, min_off = min(
            (frac, min_on, min_off), measure()
        )
    if (frac > OVERHEAD_BUDGET - 1.0
            and not os.environ.get("REPRO_CHAOS_NO_OVERHEAD_GATE")):
        raise AssertionError(
            f"chaos gate: guarded step {min_on*1e3:.2f} ms is "
            f"{frac*100:.1f}% over bare {min_off*1e3:.2f} ms "
            f"(budget {OVERHEAD_BUDGET - 1:.0%}; "
            f"REPRO_CHAOS_NO_OVERHEAD_GATE=1 to record anyway)"
        )
    return frac, min_on, min_off, trials


def run(smoke: bool = False):
    rows = []
    wall, kv = _chaos_gate()
    rows.append(row(
        "chaos/recovery_gate", wall,
        f"loss={kv.get('loss')} resumed_from={kv.get('resumed_from')} "
        f"trips={kv.get('trips')} actions={kv.get('actions')} "
        f"rungs={kv.get('rungs')}",
    ))
    frac, min_on, min_off, trials = _overhead_gate(smoke)
    # the µs column carries the dimensionless overhead fraction
    # (row() scales by 1e6, hence the /1e6) — build_snapshot lifts it
    # into the guard_overhead_frac gauge
    rows.append(row(
        "chaos/guard_overhead_frac", frac / 1e6,
        f"on={min_on*1e3:.2f}ms off={min_off*1e3:.2f}ms "
        f"trials={trials} budget={OVERHEAD_BUDGET - 1:.0%}",
    ))
    return rows
