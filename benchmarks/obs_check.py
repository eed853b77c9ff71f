"""Telemetry smoke gate (DESIGN.md §15): the observability subsystem must
be *correct* and *free*.

Three checks, all structural (absolute numbers ride the trajectory gate):

1. **Schema** — a short instrumented fused-overlap fit streams
   ``events.jsonl``; every line read back from disk must validate against
   the checked-in ``repro/obs/event_schema.json``, and the run must have
   produced a manifest and step records.
2. **Trace** — the same run's Chrome trace must contain one named planned
   issue span per bucket (distinct ``args["bucket"]`` count equals
   ``plan.num_buckets``), and a tiny serve run must emit per-request spans
   covering all three stages (prefill / insert / decode) for every request.
3. **Overhead** — an instrumented step must cost within 3% of an
   uninstrumented one on the same precompiled trainer (interleaved
   min-of-trials, the kernel_bench discipline).  This is the "near-zero
   overhead when disabled... and cheap when enabled" budget; set
   ``REPRO_OBS_NO_OVERHEAD_GATE=1`` to record without gating on a
   hopelessly noisy box.
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import jax

from .common import row

OVERHEAD_BUDGET = 1.03   # instrumented step wall <= 3% over uninstrumented
SERVE_ARCH = "qwen1.5-0.5b"
SERVE_STAGES = ("prefill", "insert", "decode")


def _validate_jsonl(path: str, schema) -> dict:
    """Parse + validate every line of an events file; returns kind counts.
    Raises on the first invalid record — the gate wants the line number."""
    from repro.obs import validate_event

    kinds: dict[str, int] = {}
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            ev = json.loads(line)
            errs = validate_event(ev, schema)
            if errs:
                raise AssertionError(
                    f"obs gate: {path}:{lineno} invalid "
                    f"{ev.get('kind')!r} event: {errs}"
                )
            kinds[ev["kind"]] = kinds.get(ev["kind"], 0) + 1
    return kinds


def _train_gate(td: str, schema, smoke: bool) -> tuple:
    """Instrumented fused-overlap fit: schema-valid JSONL + one planned
    issue span per bucket in the exported Chrome trace."""
    import repro.api as api
    from repro.obs import Telemetry
    from repro.runtime import AutotuneConfig

    tel = Telemetry(os.path.join(td, "train"))
    t0 = time.perf_counter()
    fit = api.fit(
        "gpt2-paper", reduced=True, vocab_size=256,
        compressor="covap", interval=2, overlap="fused",
        steps=6, seq_len=16, global_batch=4, log_every=1,
        # probe early and often so the audit trail (probe/replan_decision
        # events) exists within a smoke-sized run
        autotune=AutotuneConfig(measure_every=2, warmup_steps=1),
        telemetry=tel,
    )
    if fit.trainer.runtime is not None:
        fit.trainer.runtime.finish()   # planned per-bucket spans -> tracer
    wall = time.perf_counter() - t0
    paths = tel.save()
    tel.close()

    kinds = _validate_jsonl(paths["events"], schema)
    for required in ("manifest", "step", "probe", "replan_decision"):
        if not kinds.get(required):
            raise AssertionError(
                f"obs gate: instrumented fit emitted no {required!r} "
                f"events (got {kinds})"
            )

    with open(paths["trace"]) as f:
        trace = json.load(f)
    buckets = {
        ev["args"]["bucket"]
        for ev in trace["traceEvents"]
        if ev.get("cat") == "planned,issue" and "bucket" in ev.get("args", {})
    }
    want = set(range(fit.trainer.plan.num_buckets))
    if buckets != want:
        raise AssertionError(
            f"obs gate: planned issue spans cover buckets "
            f"{sorted(buckets)} != plan's {sorted(want)}"
        )
    return wall, kinds, len(want)


def _serve_gate(td: str, schema, smoke: bool) -> tuple:
    """Tiny serve run: every request must land all three stage spans (plus
    its queued span) in the shared trace, and the request/report events
    must validate."""
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.obs import Telemetry
    from repro.serve import Engine, ServeConfig, TrafficConfig, run_traffic

    cfg = get_reduced(SERVE_ARCH).with_(vocab_size=256)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tel = Telemetry(os.path.join(td, "serve"))
    eng = Engine(
        model, params,
        ServeConfig(batch_slots=2, max_len=32, max_new_tokens=4,
                    page_size=8, prefill_chunk=8),
        telemetry=tel,
    )
    n_req = 4
    t0 = time.perf_counter()
    run_traffic(eng, TrafficConfig(
        qps=32.0, num_requests=n_req, prompt_len=(2, 6),
        vocab_size=cfg.vocab_size, seed=0,
    ))
    wall = time.perf_counter() - t0
    paths = tel.save()
    tel.close()

    kinds = _validate_jsonl(paths["events"], schema)
    if kinds.get("serve_request") != n_req or not kinds.get("serve_report"):
        raise AssertionError(
            f"obs gate: serve run emitted {kinds} for {n_req} requests"
        )

    with open(paths["trace"]) as f:
        trace = json.load(f)
    per_stage: dict[str, set] = {s: set() for s in SERVE_STAGES}
    for ev in trace["traceEvents"]:
        cat = ev.get("cat", "")
        if cat.startswith("serve,"):
            stage = cat.split(",", 1)[1]
            if stage in per_stage:
                per_stage[stage].add(ev["args"]["rid"])
    for stage, rids in per_stage.items():
        if len(rids) != n_req:
            raise AssertionError(
                f"obs gate: stage {stage!r} spans for requests "
                f"{sorted(rids)}, expected all {n_req}"
            )
    return wall, {s: len(r) for s, r in per_stage.items()}


def _overhead_gate(td: str, smoke: bool) -> tuple:
    """Interleaved min-of-trials instrumented-vs-bare step wall on ONE
    precompiled trainer: both arms replay the identical step sequence from
    the same initial state (the jitted path is functional), so the only
    delta is the telemetry work — per-step counter incs, per-log-cadence
    gauge sets + one streamed JSONL record (log_every=1 here: the
    *maximally* instrumented cadence)."""
    from repro.data import DataConfig, make_loader
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.obs import NULL_TELEMETRY, Telemetry
    from repro.optim import sgd
    from repro.train.trainer import TrainConfig, Trainer, copy_train_state

    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    model = build_model(cfg)
    tc = TrainConfig(compressor="covap", interval=2, log_every=1, steps=64)
    tr = Trainer(model, sgd(1e-3, momentum=0.9), tc)
    state = tr.init_state(jax.random.PRNGKey(0))
    loader = iter(make_loader(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=16, global_batch=4,
    )))

    def silent(*_a, **_k):
        pass

    # the real per-step delta is ~25 µs (one streamed JSONL record + a few
    # gauge sets at log cadence, one counter inc per step) on a ~8 ms step
    # — ~0.3%, an order under budget — so the gate's enemy is host noise,
    # and the estimator needs depth: many short interleaved trials, min
    # per side (both sides see the same noise regime; min discards it)
    steps = 4 if smoke else 8
    trials = 5 if smoke else 9
    # run() consumes the state it is handed: every run gets a copy
    tr.run(copy_train_state(state), loader, steps=2, log=silent)  # compile
    tel = Telemetry(os.path.join(td, "overhead"))

    def timed(telemetry) -> float:
        start = jax.block_until_ready(copy_train_state(state))
        t0 = time.perf_counter()
        tr.run(start, loader, steps=steps, log=silent, telemetry=telemetry)
        return (time.perf_counter() - t0) / steps

    def measure() -> tuple:
        import gc

        gc.collect()    # don't let earlier modules' garbage bill a trial
        on, off = [], []
        for k in range(trials):
            tr.telemetry = NULL_TELEMETRY  # un-stick the previous on-trial
            # alternate pair order: a fixed off-then-on order would charge
            # any systematic second-position penalty (frequency scaling,
            # GC debt from the first run) entirely to the instrumented arm
            if k % 2 == 0:
                off.append(timed(None))
                on.append(timed(tel))
            else:
                on.append(timed(tel))
                tr.telemetry = NULL_TELEMETRY
                off.append(timed(None))
        min_on, min_off = min(on), min(off)
        return min_on / max(min_off, 1e-12) - 1.0, min_on, min_off

    # the 3% budget sits below this box's trial-to-trial scheduler noise,
    # so re-measure up to 3 rounds and gate on the best: a structural
    # regression is over budget in EVERY round, a noise spike is not
    frac, min_on, min_off = measure()
    for _ in range(2):
        if frac <= OVERHEAD_BUDGET - 1.0:
            break
        frac, min_on, min_off = min((frac, min_on, min_off), measure())
    tel.close()
    if (frac > OVERHEAD_BUDGET - 1.0
            and not os.environ.get("REPRO_OBS_NO_OVERHEAD_GATE")):
        raise AssertionError(
            f"obs gate: instrumented step {min_on*1e3:.2f} ms is "
            f"{frac*100:.1f}% over bare {min_off*1e3:.2f} ms "
            f"(budget {OVERHEAD_BUDGET - 1:.0%}; "
            f"REPRO_OBS_NO_OVERHEAD_GATE=1 to record anyway)"
        )
    return frac, min_on, min_off, trials


def run(smoke: bool = False):
    from repro.obs import load_schema

    schema = load_schema()
    rows = []
    with tempfile.TemporaryDirectory() as td:
        train_wall, kinds, n_buckets = _train_gate(td, schema, smoke)
        rows.append(row(
            "obs/train_gate", train_wall,
            f"buckets={n_buckets}/{n_buckets} "
            f"events={sum(kinds.values())} kinds={len(kinds)}",
        ))
        serve_wall, stages = _serve_gate(td, schema, smoke)
        rows.append(row(
            "obs/serve_gate", serve_wall,
            "spans=" + ",".join(f"{s}:{n}" for s, n in stages.items()),
        ))
        frac, min_on, min_off, trials = _overhead_gate(td, smoke)
        # the µs column carries the dimensionless overhead fraction
        # (row() scales by 1e6, hence the /1e6) — build_snapshot lifts it
        # into the telemetry_overhead_frac gauge
        rows.append(row(
            "obs/overhead_frac", frac / 1e6,
            f"on={min_on*1e3:.2f}ms off={min_off*1e3:.2f}ms "
            f"trials={trials} budget={OVERHEAD_BUDGET - 1:.0%}",
        ))
    return rows
