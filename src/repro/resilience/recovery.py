"""Escalating auto-recovery around the trainer's host loop (DESIGN.md §16).

The :class:`ResilienceRuntime` brackets each step:

* ``pre_step`` — pin a device copy of the clean incoming state as a
  rollback point when a new batch window opens (a copy, because the
  phase executables donate — consume — the state they are handed), write
  the guard-owned checkpoint on its cadence, then let the fault injector
  corrupt the step's inputs.  Snapshot-before-inject is load-bearing:
  skip-step must restore the state as it was before the fault, not a
  faithfully-corrupted copy.  At most TWO states are ever pinned
  (current + previous window), copied only when a window opens (every
  ``sync_every`` steps): pinning every step's state would cost a state
  copy a step and keeps the allocator from recycling step buffers
  (per-step pins once made the training step itself ~40% slower — the
  dominant guard cost, ahead of any host sync).
* ``post_step`` — run the guard battery on the step's host-side metrics
  (on ``check_every`` cadence) and, on a trip, climb the escalation
  ladder.  Checks are **deferred and batched**: step ``N``'s device
  scalars are enqueued at its own ``post_step`` and materialised —
  together with up to ``sync_every - 1`` neighbours, in step order —
  once per batch, by which time the async queue has computed them.  One
  blocking host↔device wake per batch instead of per step is what keeps
  the guard overhead inside the ≤3% budget (``benchmarks/chaos_check.py``;
  a per-step wake costs ~0.5 ms of scheduler latency on a saturated box).
  Every step is still checked; the price is detection *latency* — up to
  ``check_every * sync_every`` steps of in-flight work are discarded on
  a trip — and the adaptive runtime can see that many poisoned steps
  before the guards do: a probe or re-plan landing in the window rides
  corrupted numbers for one decision cycle.  The residual watchdog's
  norm is dispatched asynchronously at enqueue time
  (``Guards.residual_async``) so the batched flush finds it already
  computed.  ``finalize`` drains the pending batch when the loop ends:

  1. **skip-step** — discard the poisoned update by restoring (a copy
     of) the batch window's start snapshot (equivalent to zeroed
     updates: params, optimizer moments and EF residual all revert; the
     batches are consumed).  With ``sync_every=1`` this is exactly the tripped
     step's pre-state; larger windows also discard up to
     ``sync_every - 1`` clean neighbour steps.  Heals transient
     corruption.
  2. **EF flush** — restore the snapshot AND zero the error-feedback
     residual via ``runtime.transitions`` (policy ``"flush"``, through
     ``Trainer.flush_sync`` so sharded runs settle deferred gathers
     first).  Deferred gradient mass is dropped — the report records the
     norm lost — but a diverging residual cannot be skipped away: it
     re-poisons every future flush.  Residual-watchdog trips enter the
     ladder HERE: restoring the snapshot alone would also restore the
     blown-up residual and loop forever under a persistent fault.
  3. **checkpoint rewind** — restore the last guard-owned checkpoint
     (``checkpoint.restore_train_state``; digest-verified since this PR)
     and replay from there.  Loses up to ``ckpt_every`` steps; heals
     anything the snapshot itself has absorbed (e.g. slow loss-spike
     drift older than one step).

  Skip/flush budgets are **per incident** — they reset on the first
  clean check — while the rewind budget is **per run**: a workload that
  needs a third rewind is not converging, and looping the ladder forever
  would just burn the cluster.  Exhausting the ladder raises
  :class:`RecoveryError` with the trip history attached.

Honest limits: recovery is only as good as its rollback points.  A fault
the guards cannot see (silent small-magnitude corruption) gets
checkpointed as if clean; a corrupted/lost checkpoint directory fails the
digest check and ends the run (by design — restoring garbage is worse).
Mid-run process death is NOT handled here: that is the operator-restart
path (``launch/train.py --resume``), exercised by the chaos gate's
``kill`` fault.
"""
from __future__ import annotations

import time
from typing import Any

from repro.train.trainer import copy_train_state

from .faults import FaultInjector, FaultPlan, as_fault_plan
from .guards import GuardConfig, Guards, GuardTrip, as_guard_config

ACTIONS = ("skip_step", "ef_flush", "rewind")


class RecoveryError(RuntimeError):
    """The escalation ladder is exhausted (or has no rung left to climb:
    no checkpoint directory configured / no checkpoint written yet)."""

    def __init__(self, msg: str, trips: list[GuardTrip] | None = None):
        super().__init__(msg)
        self.trips = list(trips or [])


class ResilienceRuntime:
    """One per ``Trainer.run`` invocation chain (like ``AdaptiveRuntime``,
    it survives chunked runs).  Built by the trainer from
    ``run(guards=..., faults=...)``; either side may be None — guards
    without faults is the production config, faults without guards is the
    negative-control config the chaos gate uses to prove the faults are
    real."""

    def __init__(self, trainer, guards: GuardConfig | None = None,
                 faults: FaultPlan | FaultInjector | None = None,
                 telemetry=None):
        from repro.obs import as_telemetry

        self.trainer = trainer
        self.config = as_guard_config(guards)
        self.guards = Guards(self.config) if self.config is not None else None
        faults = as_fault_plan(faults)
        if isinstance(faults, FaultInjector):
            self.injector = faults
        elif faults is not None:
            self.injector = FaultInjector(faults)
        else:
            self.injector = None
        self.telemetry = as_telemetry(telemetry)
        if self.injector is not None:
            self.injector.attach_telemetry(self.telemetry)
        # rollback points: at most TWO pinned states — device copies of
        # the current batch window's start and the previous (still-
        # unflushed) window's.  Pinning one state per step (the obvious
        # design) would copy the whole state every step, and every live
        # snapshot blocks the allocator from recycling the step's buffers
        # (per-step pins once made the TRAINING STEP itself ~40% slower).
        # (step, pre-step state) | None:
        self._win: tuple[int, dict] | None = None
        self._prev_win: tuple[int, dict] | None = None
        # deferred checks, flushed in batches of sync_every:
        # (ran, device metrics, async residual norm | None) — NO state
        # reference (same allocator argument as above)
        self._pending: list[tuple[int, dict, Any]] = []
        self._last_saved_step: int | None = None
        # ladder bookkeeping
        self._skips_used = 0       # per incident
        self._flushes_used = 0     # per incident
        self._rewinds_used = 0     # per RUN — never resets
        self.actions: list[dict] = []

    # ------------------------------------------------------------------
    def attach_telemetry(self, telemetry) -> None:
        from repro.obs import as_telemetry

        self.telemetry = as_telemetry(telemetry)
        if self.injector is not None:
            self.injector.attach_telemetry(self.telemetry)

    @property
    def _cfg(self) -> GuardConfig:
        return self.config if self.config is not None else GuardConfig()

    # ------------------------------------------------------------------
    # step bracket
    # ------------------------------------------------------------------
    def pre_step(self, state: dict, batch: Any):
        """Snapshot → guard-owned checkpoint → inject.  Returns the
        (possibly corrupted) ``(state, batch)`` the step should consume."""
        cfg = self._cfg
        step = int(state["step"])
        if (
            cfg.ckpt_dir and cfg.ckpt_every > 0
            and step % cfg.ckpt_every == 0
            and step != self._last_saved_step
        ):
            state = self._save_checkpoint(state)
        # a new batch window opens when the queue is empty (run start /
        # just recovered) or full (this post_step will flush it): pin a
        # copy of this step's pre-state as the window's rollback point —
        # the step about to run consumes the state it is handed
        if not self._pending or len(self._pending) >= cfg.sync_every:
            self._prev_win = self._win
            self._win = (step, copy_train_state(state))
        if self.injector is not None:
            from .faults import InjectedCrash

            try:
                state, batch = self.injector.pre_step(state, batch, step)
            except InjectedCrash:
                # an in-process "crash": the in-flight deferred checks
                # reference a trajectory the restart will not continue
                self._pending = []
                raise
        return state, batch

    def post_step(self, state: dict, metrics: dict) -> dict:
        """Guard check + recovery, **deferred & batched**: step ``N``'s
        device scalars (and, on its cadence, an async-dispatched residual
        norm) are enqueued here; the queue is materialised in step order
        once it holds ``sync_every`` entries, by which time the async
        dispatch queue has computed them all — one blocking wake per
        batch instead of per step.  A synchronous per-step check would
        serialise host loop and device work and cost >15% of the step
        wall.  The trainer drains the final partial batch via
        :meth:`finalize`."""
        if self.guards is None:
            return state
        # flush BEFORE enqueueing the step that just dispatched: the
        # oldest batch entries are long computed, so the single blocking
        # wake waits only on the batch tail, and the in-flight step keeps
        # the device busy across it
        if len(self._pending) >= self._cfg.sync_every:
            healed = self._flush_pending(state)
            if healed is not state:
                # recovery rewound past the in-flight step too
                return healed
        # state["step"] is already advanced; guards see the step that ran
        ran = int(state["step"]) - 1
        if ran % self._cfg.check_every == 0:
            rnorm = self.guards.residual_async(ran, state.get("comp"))
            self._pending.append((ran, metrics, rnorm))
        return state

    def finalize(self, state: dict) -> dict:
        """Drain the deferred checks at the end of a run (the batched
        pipeline always leaves up to ``sync_every`` checked steps in
        flight).  May recover — the returned state can sit a few steps
        behind the loop's nominal target, but it is guarded."""
        if self.guards is None:
            self._pending = []
            return state
        return self._flush_pending(state)

    def _flush_pending(self, state: dict) -> dict:
        """Materialise the queued checks oldest-first.  On a trip the
        younger queue entries are discarded unchecked: they were computed
        from the poisoned state the trip just condemned, and recovery
        rewinds past them anyway."""
        pending, self._pending = self._pending, []
        for ran, metrics, rnorm in pending:
            host = {
                k: float(v) for k, v in metrics.items()
                if k in ("total_loss", "loss", "grad_norm")
            }
            trips = self.guards.check(
                ran, host,
                residual_value=None if rnorm is None else float(rnorm),
            )
            if not trips:
                # first clean check closes the incident: the next fault
                # gets the full skip/flush budget again (rewinds stay
                # spent)
                self._skips_used = 0
                self._flushes_used = 0
                continue
            for t in trips:
                self._emit_trip(t)
            return self._recover(ran, trips)
        return state

    # ------------------------------------------------------------------
    # the ladder
    # ------------------------------------------------------------------
    def _recover(self, step: int, trips: list[GuardTrip]) -> dict:
        cfg = self._cfg
        residual_trip = any(t.guard == "residual" for t in trips)
        if cfg.retry_backoff_s > 0.0:
            time.sleep(cfg.retry_backoff_s)

        # residual trips enter at the flush rung (skip would restore the
        # blown-up residual along with everything else)
        if not residual_trip and self._skips_used < cfg.max_skips:
            self._skips_used += 1
            return self._act("skip_step", step, self._skip(step),
                             attempt=self._skips_used,
                             detail=trips[0].reason)
        if self._flushes_used < cfg.max_flushes:
            self._flushes_used += 1
            return self._act("ef_flush", step, self._flush(step),
                             attempt=self._flushes_used,
                             detail=trips[0].reason)
        if self._rewinds_used < cfg.max_rewinds:
            restored, rewind_to = self._rewind(step, trips)
            self._rewinds_used += 1
            # a rewind opens a fresh incident at the restored step
            self._skips_used = 0
            self._flushes_used = 0
            self.guards.reset_window()
            return self._act("rewind", step, restored,
                             attempt=self._rewinds_used,
                             detail=trips[0].reason, rewind_to=rewind_to)
        raise RecoveryError(
            f"recovery ladder exhausted at step {step}: "
            f"{self._skips_used} skip(s), {self._flushes_used} flush(es), "
            f"{self._rewinds_used} rewind(s) "
            f"(last trip: {trips[0].guard}: {trips[0].reason})",
            trips=self.guards.trips,
        )

    def _skip(self, step: int) -> dict:
        """Roll back to the tightest window snapshot at or before the
        tripped step: exactly its pre-step state when ``sync_every=1``,
        else the start of the batch window it ran in (discarding up to
        ``sync_every - 1`` clean neighbours — the price of pinning only
        two rollback states, see ``__init__``).  Hands out a copy: the
        next step consumes it, and the snapshot stays pinned."""
        best = None
        for w in (self._prev_win, self._win):
            if w is not None and w[0] <= step:
                if best is None or w[0] > best[0]:
                    best = w
        if best is None:
            raise RecoveryError("no pre-step snapshot to skip back to")
        return copy_train_state(best[1])

    def _flush(self, step: int) -> dict:
        from repro.runtime.transitions import carry_comp_state

        tr = self.trainer
        state = tr.flush_sync(self._skip(step))
        interval = tr.tc.interval
        comp, report = carry_comp_state(
            state["comp"], new_compressor=tr.compressor, new_plan=tr.plan,
            params_like=state["params"], step=step,
            old_interval=interval, new_interval=interval, policy="flush",
        )
        tr.transitions.append(report)
        return {**state, "comp": comp}

    def _rewind(self, step: int, trips: list[GuardTrip]) -> tuple[dict, int]:
        from repro import checkpoint

        cfg = self._cfg
        if not cfg.ckpt_dir:
            raise RecoveryError(
                f"guard trip at step {step} needs a checkpoint rewind but "
                f"GuardConfig.ckpt_dir is not set",
                trips=trips,
            )
        last = checkpoint.latest_step(cfg.ckpt_dir)
        if last is None:
            raise RecoveryError(
                f"guard trip at step {step} needs a checkpoint rewind but "
                f"{cfg.ckpt_dir!r} holds no checkpoint yet",
                trips=trips,
            )
        like = (
            self._win[1] if self._win is not None
            else self._prev_win[1] if self._prev_win is not None
            else {}
        )
        state, _extra = checkpoint.restore_train_state(cfg.ckpt_dir, like)
        return state, int(last)

    def _save_checkpoint(self, state: dict) -> dict:
        from repro import checkpoint

        cfg = self._cfg
        tr = self.trainer
        state = tr.flush_sync(state)     # sharded: persist fresh params
        path = checkpoint.save_train_state(
            cfg.ckpt_dir, state, interval=tr.tc.interval,
            extra={"guard_owned": True},
        )
        self._last_saved_step = int(state["step"])
        if self.telemetry.enabled:
            self.telemetry.events.emit(
                "checkpoint", step=int(state["step"]), path=path,
            )
        return state

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def _emit_trip(self, t: GuardTrip) -> None:
        tel = self.telemetry
        if not tel.enabled:
            return
        import math

        tel.events.emit(
            "guard_trip", step=int(t.step), guard=t.guard, reason=t.reason,
            value=None if not math.isfinite(t.value) else float(t.value),
            threshold=(
                None if not math.isfinite(t.threshold) else float(t.threshold)
            ),
        )
        tel.registry.counter(
            "guard_trips_total", "numeric guard trips, by guard",
            guard=t.guard,
        ).inc()

    def _act(self, action: str, step: int, state: dict, *, attempt: int,
             detail: str, rewind_to: int | None = None) -> dict:
        rec = {"step": step, "action": action, "attempt": attempt,
               "detail": detail}
        if rewind_to is not None:
            rec["rewind_to"] = rewind_to
        self.actions.append(rec)
        tel = self.telemetry
        if tel.enabled:
            kw = {} if rewind_to is None else {"rewind_to": int(rewind_to)}
            tel.events.emit(
                "recovery", step=step, action=action, ok=True,
                attempt=attempt, detail=detail, **kw,
            )
            tel.registry.counter(
                "recovery_actions_total", "recovery ladder actions, by rung",
                action=action,
            ).inc()
        return state

    # ------------------------------------------------------------------
    def summary(self) -> dict:
        out = {
            "trips": len(self.guards.trips) if self.guards else 0,
            "trips_by_guard": {},
            "actions": len(self.actions),
            "actions_by_rung": {},
            "rewinds_used": self._rewinds_used,
        }
        if self.guards:
            for t in self.guards.trips:
                out["trips_by_guard"][t.guard] = (
                    out["trips_by_guard"].get(t.guard, 0) + 1
                )
        for a in self.actions:
            out["actions_by_rung"][a["action"]] = (
                out["actions_by_rung"].get(a["action"], 0) + 1
            )
        if self.injector is not None:
            out["faults"] = self.injector.summary()
        return out


__all__ = ["ACTIONS", "RecoveryError", "ResilienceRuntime"]
