"""Resilience subsystem: deterministic faults, guard trips, the recovery
ladder, crash-safe checkpoints, and the controller circuit breaker
(DESIGN.md §16).

The multi-worker chaos test runs in a subprocess (8 fake CPU devices must
be configured before jax initialises); everything else is in-process on
the tiny reduced configs.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import checkpoint
from repro.checkpoint import CheckpointCorruptError
from repro.configs import get_reduced
from repro.data import DataConfig, make_loader
from repro.models import build_model
from repro.obs import Telemetry, validate_event
from repro.optim import adamw
from repro.resilience import (
    FaultEvent,
    FaultInjector,
    FaultPlan,
    GuardConfig,
    Guards,
    InjectedCrash,
    RecoveryError,
    blowup_residual,
    corrupt_planes,
    corrupt_tree,
    parse_fault_spec,
    plane_nonfinite_counts,
)
from repro.runtime.controller import AutotuneConfig, ReplanController
from repro.train.trainer import TrainConfig, Trainer

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# faults: deterministic, reproducible corruption
# ---------------------------------------------------------------------------

def test_parse_fault_spec_grammar():
    plan = parse_fault_spec("grad_nan@6, ef_blowup@12*1e9, grad_inf@18x4")
    kinds = [(e.kind, e.step, e.times) for e in plan.events]
    assert kinds == [("grad_nan", 6, 1), ("ef_blowup", 12, 1),
                     ("grad_inf", 18, 4)]
    assert plan.events[1].scale == 1e9
    with pytest.raises(ValueError):
        parse_fault_spec("grad_nan")           # missing @step
    with pytest.raises(ValueError):
        parse_fault_spec("not_a_fault@3")      # unknown kind


def test_corrupt_tree_is_deterministic_and_minimal():
    tree = {"a": jnp.ones((8, 8)), "b": jnp.ones((32,))}
    out1, sites1 = corrupt_tree(tree, "grad_nan", seed=7, step=11, count=3)
    out2, sites2 = corrupt_tree(tree, "grad_nan", seed=7, step=11, count=3)
    assert sites1 == sites2
    n_bad = sum(int(jnp.sum(~jnp.isfinite(x))) for x in jax.tree.leaves(out1))
    assert n_bad == 3
    # different step -> different sites (the schedule, not the call count,
    # drives site selection)
    _, sites3 = corrupt_tree(tree, "grad_nan", seed=7, step=12, count=3)
    assert sites3 != sites1


def test_corrupt_planes_and_plane_guard():
    """The one-reduction-per-plane guard sees exactly the injected
    corruption on packed arena planes."""
    planes = [jnp.zeros(64), jnp.zeros(128), jnp.zeros(16)]
    assert plane_nonfinite_counts(planes) == [0, 0, 0]
    bad, sites = corrupt_planes(planes, "grad_inf", seed=0, step=3, count=4)
    counts = plane_nonfinite_counts(bad)
    assert sum(counts) == 4
    for li, _ in sites:
        assert counts[li] > 0


def test_bitflip_is_a_blowup_not_a_wiggle():
    tree = {"w": jnp.ones((64,))}
    out, sites = corrupt_tree(tree, "grad_bitflip", seed=1, step=5)
    (_, fi), = sites
    v = float(out["w"][fi])
    # a high-exponent-bit flip moves the value by many orders of magnitude
    # (up or down, depending on whether the bit was set) — never a wiggle
    assert not math.isfinite(v) or v == 0.0 or abs(math.log10(abs(v))) > 3


def test_blowup_residual_scales_floating_leaves():
    comp = {"r": jnp.full((4,), 2.0), "i": jnp.arange(3)}
    out = blowup_residual(comp, 1e10)
    assert float(out["r"][0]) == pytest.approx(2e10)
    assert out["i"].dtype == comp["i"].dtype          # ints untouched


def test_kill_fault_raises_injected_crash():
    inj = FaultInjector(FaultPlan(events=(FaultEvent(step=4, kind="kill"),)))
    state = {"params": {"w": jnp.ones(2)}, "comp": (), "step": 4}
    with pytest.raises(InjectedCrash):
        inj.pre_step(state, None, 4)
    # exhausted: the restart that resumes past step 4 is not re-killed
    state2, _ = inj.pre_step(state, None, 4)
    assert state2 is state


def test_fault_firing_budget_times():
    ev = FaultEvent(step=2, kind="grad_nan", times=2)
    inj = FaultInjector(FaultPlan(events=(ev,)))
    state = {"params": {"w": jnp.ones(4)}, "comp": (), "step": 2}
    for expect_poison in (True, True, False):
        out, _ = inj.pre_step(state, None, 2)
        poisoned = bool(jnp.any(~jnp.isfinite(out["params"]["w"])))
        assert poisoned == expect_poison


# ---------------------------------------------------------------------------
# guards
# ---------------------------------------------------------------------------

def test_guard_nonfinite_and_window_hygiene():
    g = Guards(GuardConfig())
    assert g.check(0, {"total_loss": 1.0, "grad_norm": 1.0}) == []
    trips = g.check(1, {"total_loss": float("inf"), "grad_norm": 1.0})
    assert [t.guard for t in trips] == ["nonfinite"]
    # the tripped loss must NOT enter the spike window
    assert all(math.isfinite(x) for x in g._losses)
    trips = g.check(2, {"total_loss": 1.0, "grad_norm": float("nan")})
    assert [t.guard for t in trips] == ["nonfinite"]


def test_guard_loss_spike_median_window():
    g = Guards(GuardConfig(loss_spike_min_steps=4, loss_spike_factor=10.0))
    for i in range(6):
        assert g.check(i, {"total_loss": 2.0 + 0.01 * i}) == []
    trips = g.check(6, {"total_loss": 50.0})
    assert [t.guard for t in trips] == ["loss_spike"]
    # not armed before min_steps
    g2 = Guards(GuardConfig(loss_spike_min_steps=4, loss_spike_factor=10.0))
    g2.check(0, {"total_loss": 1.0})
    assert g2.check(1, {"total_loss": 1000.0}) == []


def test_guard_residual_watchdog_cadence():
    cfg = GuardConfig(residual_check_every=4, residual_abs_max=1e6)
    g = Guards(cfg)
    comp = {"r": jnp.full((8,), 1e5)}     # norm ~2.8e5: under the limit
    assert g.check(4, {"total_loss": 1.0}, comp) == []
    hot = blowup_residual(comp, 1e8)
    # off-cadence step: watchdog silent even though the residual is hot
    assert g.check(5, {"total_loss": 1.0}, hot) == []
    trips = g.check(8, {"total_loss": 1.0}, hot)
    assert [t.guard for t in trips] == ["residual"]


# ---------------------------------------------------------------------------
# the recovery ladder end-to-end (single process)
# ---------------------------------------------------------------------------

def _tiny_trainer(steps_cfg=24, interval=2):
    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    model = build_model(cfg)
    tc = TrainConfig(compressor="covap", interval=interval,
                     bucket_bytes=1 << 14, max_buckets=16,
                     log_every=1000, steps=steps_cfg)
    tr = Trainer(model, adamw(3e-3), tc)
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4,
                    corpus_tokens=1 << 12)
    return model, tr, state, iter(make_loader(dc))


def test_ladder_all_rungs_with_schema_valid_telemetry(tmp_path):
    """Every injected fault must surface as schema-valid guard_trip /
    recovery / fault_injected events with matching counter increments, and
    the run must end with finite loss."""
    model, tr, state, loader = _tiny_trainer()
    tel = Telemetry(str(tmp_path / "tel"))
    g = GuardConfig(ckpt_dir=str(tmp_path / "ck"), ckpt_every=6,
                    residual_check_every=2, max_skips=1, max_flushes=1,
                    sync_every=1)   # strict lag-one: step-exact schedule
    # 40 loop iterations: every fault firing costs two (the poisoned step
    # plus the lag-one detection step) and the rewind replays from the
    # step-12 checkpoint, so 24 nominal steps of progress need headroom
    state = tr.run(state, loader, steps=40, log=None, telemetry=tel,
                   guards=g, faults="grad_nan@8,ef_blowup@12,grad_inf@16x3")
    loss = float(model.loss_fn(state["params"], next(loader))[0])
    assert math.isfinite(loss)

    s = tr.resilience.summary()
    # all three rungs exercised by this schedule (1 skip budget + 1 flush
    # budget per incident, grad_inf fires 3x -> forced up to a rewind)
    assert set(s["actions_by_rung"]) == {"skip_step", "ef_flush", "rewind"}
    assert s["faults"]["fired"] >= 4

    tel.save()
    tel.close()
    by_kind: dict[str, list[dict]] = {}
    with open(tmp_path / "tel" / "events.jsonl") as f:
        for line in f:
            ev = json.loads(line)
            by_kind.setdefault(ev["kind"], []).append(ev)
            validate_event(ev)     # schema-valid on disk, not just at emit
    # every trip / action / firing visible in telemetry, 1:1 with counters
    snap = tel.registry.snapshot()
    n_trips = sum(v for k, v in snap.items()
                  if k.startswith("guard_trips_total"))
    n_actions = sum(v for k, v in snap.items()
                    if k.startswith("recovery_actions_total"))
    n_faults = sum(v for k, v in snap.items()
                   if k.startswith("faults_injected_total"))
    assert len(by_kind["guard_trip"]) == n_trips == s["trips"]
    assert len(by_kind["recovery"]) == n_actions == s["actions"]
    assert len(by_kind["fault_injected"]) == n_faults == s["faults"]["fired"]
    rungs = {e["action"] for e in by_kind["recovery"]}
    assert rungs == {"skip_step", "ef_flush", "rewind"}
    assert any("rewind_to" in e for e in by_kind["recovery"])


def test_skip_step_restores_pre_fault_state():
    """One transient NaN: the recovered run's state at the re-run step must
    be bit-identical to an unfaulted run fed the same batches (skip-step
    restores the pre-corruption snapshot; the poisoned batch AND the
    lag-one detection step's batch are consumed)."""
    model, tr, state, _ = _tiny_trainer()
    dc = DataConfig(vocab_size=256, seq_len=16, global_batch=4,
                    corpus_tokens=1 << 12)
    batches = list(b for b, _ in zip(make_loader(dc), range(16)))

    def run(faults):
        m, t, s, _ = _tiny_trainer()
        # faulted runs burn two batches on a skipped incident: give both
        # the same stream and compare at equal STEP, not equal batch count
        # (sync_every=1 pins the strict lag-one check so the batch
        # arithmetic below is exact; also exercises the dict-override path)
        s = t.run(s, iter(batches), steps=12, log=None,
                  guards={"sync_every": 1}, faults=faults)
        return s

    clean = run(None)
    healed = run("grad_nan@5")
    # fault at step 5 (batch 5), detected at the lag-one check during
    # step 6 (batch 6) -> both discarded, 10 real steps in 12 iterations
    assert int(healed["step"]) == 10
    m, t, s, _ = _tiny_trainer()
    replay_batches = batches[:5] + batches[7:12]
    replayed = t.run(s, iter(replay_batches), steps=10, log=None)
    for a, b in zip(jax.tree.leaves(healed["params"]),
                    jax.tree.leaves(replayed["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_skip_step_under_donating_step_keeps_pinned_snapshot(monkeypatch):
    """The step consumes the state it is handed, so the rollback point is
    a pinned copy: a skip-step back to it hands the step a copy of its
    own and leaves the snapshot alive, and the resumed run equals the
    unfaulted run fed the same batches."""
    from repro.resilience.recovery import ResilienceRuntime

    dc = DataConfig(vocab_size=256, seq_len=16, global_batch=4,
                    corpus_tokens=1 << 12)
    batches = list(b for b, _ in zip(make_loader(dc), range(16)))
    skipped = []
    skip = ResilienceRuntime._skip

    def spy(self, step):
        restored = skip(self, step)
        pinned = max((w for w in (self._prev_win, self._win)
                      if w is not None and w[0] <= step), key=lambda w: w[0])
        skipped.append((pinned[1], restored))
        return restored

    monkeypatch.setattr(ResilienceRuntime, "_skip", spy)
    model, tr, state, _ = _tiny_trainer()
    tel = Telemetry()
    healed = tr.run(state, iter(batches), steps=12, log=None, telemetry=tel,
                    guards={"sync_every": 1}, faults="grad_nan@5")
    assert tr.resilience.summary()["actions_by_rung"] == {"skip_step": 1}
    shares = [v for k, v in tel.registry.snapshot().items()
              if k.startswith("train_state_donated_share")]
    assert shares and all(v == 1.0 for v in shares)

    def arrays(s):
        return jax.tree.leaves([s[k] for k in ("params", "opt", "comp")])

    (pinned, restored), = skipped
    assert all(x.is_deleted() for x in arrays(restored))   # consumed
    assert not any(x.is_deleted() for x in arrays(pinned))  # still pinned
    assert int(pinned["step"]) == 5
    m, t, s, _ = _tiny_trainer()
    replayed = t.run(s, iter(batches[:5] + batches[7:12]), steps=10,
                     log=None)
    assert int(healed["step"]) == int(replayed["step"]) == 10
    for a, b in zip(arrays(healed), arrays(replayed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_sync_detection_and_recovery():
    """Default ``sync_every=4`` batches the deferred checks: detection is
    late (up to a full batch window of work is discarded) but still
    deterministic — flushes are counted in steps, not wall time — and
    every step is still checked.  One transient NaN at step 5: the batch
    [4..7] flushes at iteration 8, trips on 5, and skip-step rolls back
    to the window-start snapshot (step 4), discarding the poisoned step,
    its clean neighbours 4/6/7 and the in-flight step 8 — 5 of 16
    iterations, netting exactly 11 committed steps."""
    model, tr, state, loader = _tiny_trainer()
    state = tr.run(state, loader, steps=16, log=None, guards=True,
                   faults="grad_nan@5")
    assert int(state["step"]) == 11
    s = tr.resilience.summary()
    assert s["actions_by_rung"] == {"skip_step": 1}
    assert s["trips_by_guard"] == {"nonfinite": 1}
    # the trip is attributed to the step that ran, not the flush point
    assert tr.resilience.guards.trips[0].step == 5
    loss = float(model.loss_fn(state["params"], next(loader))[0])
    assert math.isfinite(loss)


def test_guard_config_validates_sync_every():
    with pytest.raises(ValueError, match="sync_every"):
        GuardConfig(sync_every=0)


def test_ladder_exhaustion_raises_recovery_error():
    model, tr, state, loader = _tiny_trainer()
    g = GuardConfig(max_skips=1, max_flushes=0, max_rewinds=0)
    with pytest.raises(RecoveryError) as ei:
        tr.run(state, loader, steps=12, log=None, guards=g,
               faults="grad_nan@4x8")
    assert ei.value.trips      # the trip history rides the exception


def test_rewind_without_ckpt_dir_raises():
    model, tr, state, loader = _tiny_trainer()
    g = GuardConfig(max_skips=0, max_flushes=0, max_rewinds=2)  # rewind-only
    with pytest.raises(RecoveryError, match="ckpt_dir"):
        tr.run(state, loader, steps=8, log=None, guards=g,
               faults="grad_nan@3")


def test_guards_off_path_bit_identical():
    """guards=None must leave the training trajectory untouched."""
    def run(**kw):
        m, t, s, _ = _tiny_trainer()
        dc = DataConfig(vocab_size=256, seq_len=16, global_batch=4,
                        corpus_tokens=1 << 12)
        return t.run(s, iter(make_loader(dc)), steps=6, log=None, **kw)

    a = run()
    b = run(guards=True)
    for x, y in zip(jax.tree.leaves(a["params"]), jax.tree.leaves(b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_ccr_skew_inflates_probe():
    from repro.runtime.monitor import PhaseSample

    def probe(state, batch, phase):
        return PhaseSample(t_comp=1.0, t_comm=0.5, phase=phase, step=0)

    inj = FaultInjector(FaultPlan(events=(
        FaultEvent(step=1, kind="ccr_skew", times=2, scale=3.0),
    )))
    wrapped = inj.wrap_probe(probe)
    s0 = wrapped(None, None, 0)     # probe call 0: before the event
    s1 = wrapped(None, None, 0)     # probe calls 1,2: skewed
    s2 = wrapped(None, None, 0)
    s3 = wrapped(None, None, 0)     # budget exhausted
    assert s0.t_comm == 0.5 and s3.t_comm == 0.5
    assert s1.t_comm == pytest.approx(3.5) and s2.t_comm == pytest.approx(3.5)


# ---------------------------------------------------------------------------
# crash-safe checkpoint store
# ---------------------------------------------------------------------------

def _state():
    return {"params": {"w": jnp.arange(12.0), "b": jnp.ones(3)},
            "opt": {"m": {"w": jnp.zeros(12), "b": jnp.zeros(3)}},
            "comp": {"w": jnp.zeros(12), "b": jnp.zeros(3)}, "step": 7}


def test_checkpoint_digest_roundtrip(tmp_path):
    d = str(tmp_path)
    p = checkpoint.save_train_state(d, _state(), interval=2)
    with open(os.path.join(p, "manifest.json")) as f:
        man = json.load(f)
    assert man["digest"].startswith("sha256:")
    assert checkpoint.verify(d, 7) == man["digest"]
    restored, extra = checkpoint.restore_train_state(d, _state())
    assert extra["interval"] == 2
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]), np.arange(12.0)
    )


def test_checkpoint_corruption_detected(tmp_path):
    d = str(tmp_path)
    p = checkpoint.save_train_state(d, _state(), interval=2)
    npz = os.path.join(p, "arrays.npz")
    data = bytearray(open(npz, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(npz, "wb").write(bytes(data))
    with pytest.raises(CheckpointCorruptError):
        checkpoint.restore_train_state(d, _state())
    # the comp-drift fallback must NOT swallow corruption either
    with pytest.raises(CheckpointCorruptError):
        checkpoint.restore(d, 7, {"params": _state()["params"]})


def test_checkpoint_partial_write_detected(tmp_path):
    d = str(tmp_path)
    p = checkpoint.save_train_state(d, _state(), interval=2)
    os.remove(os.path.join(p, "arrays.npz"))
    with pytest.raises(CheckpointCorruptError, match="no arrays.npz"):
        checkpoint.restore_train_state(d, _state())


def test_checkpoint_save_is_atomic_and_overwrites(tmp_path):
    d = str(tmp_path)
    checkpoint.save_train_state(d, _state(), interval=2)
    # temp staging dirs are invisible to latest_step's scan
    assert checkpoint.latest_step(d) == 7
    assert not any(n.startswith(".tmp") for n in os.listdir(d))
    # re-save at the same step (e.g. rewind then re-checkpoint): replaced
    # atomically, still restorable
    s2 = _state()
    s2["params"] = {"w": jnp.full(12, 9.0), "b": jnp.ones(3)}
    checkpoint.save_train_state(d, s2, interval=4)
    restored, extra = checkpoint.restore_train_state(d, _state())
    assert float(restored["params"]["w"][0]) == 9.0 and extra["interval"] == 4


def test_pre_digest_checkpoints_still_restore(tmp_path):
    """Backward compat: a manifest without a digest restores (nothing to
    verify) rather than failing the new check."""
    d = str(tmp_path)
    p = checkpoint.save_train_state(d, _state(), interval=2)
    mpath = os.path.join(p, "manifest.json")
    man = json.load(open(mpath))
    del man["digest"]
    json.dump(man, open(mpath, "w"))
    restored, _ = checkpoint.restore_train_state(d, _state())
    assert int(restored["step"]) == 7


# ---------------------------------------------------------------------------
# controller: oscillation property + circuit breaker
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(
    lo=st.floats(min_value=0.5, max_value=3.0),
    hi=st.floats(min_value=4.0, max_value=32.0),
    period=st.integers(min_value=1, max_value=5),
    cooldown=st.integers(min_value=1, max_value=64),
    patience=st.integers(min_value=1, max_value=3),
    max_replans=st.integers(min_value=1, max_value=8),
)
def test_adversarial_ccr_trace_bounded_by_max_replans(
    lo, hi, period, cooldown, patience, max_replans,
):
    """PROPERTY: no alternating-CCR trace can trigger more than
    max_replans replans, breaker or no breaker."""
    cfg = AutotuneConfig(
        patience=patience, cooldown_steps=cooldown, max_replans=max_replans,
        breaker_replans=0,       # breaker off: max_replans alone must hold
    )
    ctl = ReplanController(cfg, interval=2)
    for i in range(400):
        ccr = lo if (i // period) % 2 == 0 else hi
        ctl.observe(i, ccr)
    assert ctl.replans <= max_replans
    assert len(ctl.replan_steps) == ctl.replans


@settings(max_examples=25, deadline=None)
@given(
    hi=st.floats(min_value=6.0, max_value=40.0),
    breaker=st.integers(min_value=2, max_value=5),
)
def test_breaker_latches_on_thrash_and_freezes(hi, breaker):
    """PROPERTY: under a worst-case flapping trace the breaker latches
    after exactly breaker_replans replans in its window, and no replan
    ever lands afterwards."""
    cfg = AutotuneConfig(
        patience=1, cooldown_steps=1, max_replans=10 ** 6,
        breaker_replans=breaker, breaker_window_steps=10 ** 6,
    )
    ctl = ReplanController(cfg, interval=2)
    for i in range(300):
        ccr = 1.0 if i % 2 == 0 else hi
        ctl.observe(i, ccr)
    assert ctl.frozen
    assert ctl.replans == breaker
    replans_at_latch = ctl.replans
    for i in range(300, 340):
        d = ctl.observe(i, hi if i % 2 else 1.0)
        assert not d.replan
        assert d.reason.startswith("circuit-open:")
    assert ctl.replans == replans_at_latch


def test_breaker_window_expiry_and_reset():
    cfg = AutotuneConfig(
        patience=1, cooldown_steps=1, max_replans=10 ** 6,
        breaker_replans=3, breaker_window_steps=10,
    )
    ctl = ReplanController(cfg, interval=2)
    # two replans, then a long quiet gap: the window forgets them
    ctl.observe(0, 8.0)
    ctl.observe(100, 1.0)
    assert ctl.replans == 2 and not ctl.frozen
    ctl.observe(300, 8.0)
    assert ctl.replans == 3 and not ctl.frozen   # only 1 in-window replan
    # three rapid replans latch it
    ctl.observe(301, 1.0)
    ctl.observe(302, 8.0)
    assert ctl.frozen
    ctl.reset_breaker()
    assert not ctl.frozen and ctl.replan_steps == []


# ---------------------------------------------------------------------------
# 8-worker mesh chaos: finite loss + bit-for-bit restorable state
# ---------------------------------------------------------------------------

def run_sub(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_mesh_chaos_run_finite_and_restorable(tmp_path):
    out = run_sub(f"""
    import json, math, os, tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro import checkpoint
    from repro.configs import get_reduced
    from repro.data import DataConfig, make_loader
    from repro.models import build_model
    from repro.optim import adamw
    from repro.resilience import GuardConfig
    from repro.train.trainer import TrainConfig, Trainer

    td = {str(tmp_path)!r}
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    model = build_model(cfg)
    tc = TrainConfig(compressor="covap", interval=2, bucket_bytes=1 << 14,
                     max_buckets=16, log_every=1000)
    tr = Trainer(model, adamw(3e-3), tc, mesh=mesh, dp_axes=("data",))
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=256, seq_len=16, global_batch=8,
                    corpus_tokens=1 << 12)
    g = GuardConfig(ckpt_dir=os.path.join(td, "ck"), ckpt_every=6,
                    residual_check_every=2, max_skips=1, max_flushes=1)
    loader = iter(make_loader(dc))
    state = tr.run(state, loader, steps=20, log=None,
                   guards=g, faults="grad_nan@7,ef_blowup@11")
    s = tr.resilience.summary()
    assert s["actions"] >= 2, s

    # bit-for-bit restorable: save the final (flushed) state, restore into
    # a fresh trainer, compare every leaf exactly
    p = checkpoint.save_train_state(os.path.join(td, "final"), state,
                                    interval=tr.tc.interval)
    tr2 = Trainer(model, adamw(3e-3), tc, mesh=mesh, dp_axes=("data",))
    like = tr2.init_state(jax.random.PRNGKey(1))
    restored, extra = checkpoint.restore_train_state(os.path.join(td, "final"), like)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # finite loss after the chaos: one more compiled step reads the metric
    # through the trainer's own sharding-aware executable
    fn = tr._phase_fn(int(state["step"]) % tr.num_phases)
    _, _, _, m = fn(state["params"], state["opt"], state["comp"],
                    next(loader), jnp.asarray(state["step"], jnp.int32))
    loss = float(m["total_loss"])
    assert math.isfinite(loss), loss
    print("MESHCHAOS ok loss=%.4f actions=%d" % (loss, s["actions"]))
    """)
    assert "MESHCHAOS ok" in out
