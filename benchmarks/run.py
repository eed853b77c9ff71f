"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2,fig5] [--smoke]

Prints ``name,us_per_call,derived`` CSV (one line per measurement).
``--smoke`` runs the fast CI subset used by scripts/ci.sh: mostly
analytic/plan-level modules plus two compiled-HLO gates ("overlap",
"arena"), then records a standardized ``BENCH_<n>.json`` snapshot (step
wall time from a small measured covap run — the one genuinely trained
piece, ~15 s — bytes/worker, modeled overlap fraction, pack-kernel µs)
so the perf trajectory of the repo is tracked PR over PR.  The snapshot
is written only for the full smoke set (not with ``--only``); the
``BENCH_*.json`` pattern is gitignored — ``git add -f`` the snapshot a
PR means to record.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time
import traceback

import inspect

from repro.launch.compile_cache import enable_compile_cache

from . import (
    adaptive_runtime,
    arena_check,
    chaos_check,
    fig5_ratio_sweep,
    fig11_scaling,
    hier_check,
    kernel_bench,
    obs_check,
    overlap_check,
    serve_bench,
    sharded_check,
    table1_ccr,
    table2_overhead,
    table3_gc_overlap,
    table5_sharding,
    table7_training,
)
from .common import emit

MODULES = {
    "table1": table1_ccr,
    "table2": table2_overhead,
    "table3": table3_gc_overlap,
    "table5": table5_sharding,
    "table7": table7_training,
    "fig5": fig5_ratio_sweep,
    "fig11": fig11_scaling,
    "kernels": kernel_bench,
    "adaptive": adaptive_runtime,
    "overlap": overlap_check,
    "arena": arena_check,
    "sharded": sharded_check,
    "hier": hier_check,
    "serve": serve_bench,
    "obs": obs_check,
    "chaos": chaos_check,
}

# fast modules only: no training loops, no heavy jit — the CI smoke gate.
# "kernels" runs here in its reduced --smoke size so scripts/ci.sh bench
# exercises the Pallas kernel reference path on every run; "overlap" is the
# HLO interleaving gate (compiles ONE fused step on an 8-worker CPU mesh
# and fails unless collectives are scheduled inside the backward pass);
# "arena" is the zero-copy gate (fails unless the arena build issues fewer
# data-movement ops than the concat path); "sharded" is the sharded-sync
# placement gate (fails unless the compiled sharded step reduce-scatters
# before the final gradient fusion with the deferred param all-gathers at
# the step head, and the exposed wire bytes are <= 0.6x all-reduce);
# "hier" is the two-level hierarchical gate (benchmarks/hier_check.py:
# compiles one sharded step on a (pod=2, data=4) mesh and fails unless the
# CommSchedule's per-link byte accounting — intra-pod RS + deferred AG on
# the ICI, owned-shard exchanges on the DCN — matches the compiled HLO's
# replica-group-classified collective bytes); "serve" is the serving gate (short QPS sweep through the paged-KV
# continuous-batching engine; fails on lost requests, invalid finish
# reasons, or prefill degenerating to one call per token); "obs" is the
# telemetry gate (benchmarks/obs_check.py: an instrumented run must emit
# schema-valid JSONL + a Chrome trace with one named planned span per
# bucket + per-request serve spans, and the instrumented step wall must
# stay within 3% of the uninstrumented one); "chaos" is the resilience
# gate (benchmarks/chaos_check.py: an 8-worker mesh run under injected
# NaN grads + EF blow-up + a mid-run kill must heal through all three
# recovery rungs with every trip in telemetry, and a guarded step must
# stay within 3% of an unguarded one — recorded as guard_overhead_frac).
SMOKE_MODULES = ("table1", "table3", "table5", "fig5", "fig11", "kernels",
                 "adaptive", "overlap", "arena", "sharded", "hier", "serve",
                 "obs", "chaos")

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def build_snapshot(all_rows: list[tuple]) -> dict:
    """The standardized perf digest recorded per PR: a tiny measured covap
    run (per-step wall time, arena off/on), the static plan's byte and
    overlap accounting, the pack-kernel microbenchmark, and the serving
    gate's stage/latency numbers.

    Since schema 3 every value flows through a ``repro.obs``
    :class:`MetricsRegistry` — the snapshot body IS ``registry.snapshot()``
    (DESIGN.md §15): a perf key exists in ``BENCH_<n>.json`` iff a gauge
    recorded it, so the BENCH schema and the telemetry schema cannot
    drift apart."""
    import repro.api as api
    from repro.obs import MetricsRegistry

    def measured_step(arena: bool):
        t0 = time.perf_counter()
        r = api.fit(
            "gpt2-paper", reduced=True, vocab_size=256, interval=4,
            steps=8, seq_len=32, global_batch=8, arena=arena,
        )
        # amortised per-step wall (includes the 4 phase compiles — a
        # stable smoke-sized proxy, tracked relative over PRs)
        return (time.perf_counter() - t0) / 8, r

    # interleaved min-of-trials (the kernel_bench discipline): alternating
    # off/on trials share whatever transient load the host is under, and
    # min-of-3 discards scheduler noise — step_wall_s moved 1.00->1.74 s
    # between BENCH_0/1 on an unchanged workload with the single-shot
    # measurement this replaces.
    walls_off, walls_on = [], []
    fit = None
    for _ in range(3):
        w_off, r = measured_step(False)
        walls_off.append(w_off)
        if fit is None:
            fit = r
        w_on, _ = measured_step(True)
        walls_on.append(w_on)
    wall_off, wall_on = min(walls_off), min(walls_on)
    report = fit.trainer.schedule_report()
    # the modeled overlap column prices the PAPER's workload — full
    # gpt2-paper at seq 1024 / global batch 512 over 64 workers, the
    # regime where CCR ≈ 3 and COVAP's I=4 hides ~94% of the comm.
    # Through BENCH_2 this row was priced on the SMOKE workload above
    # (256 tokens/step on the 30 Gbps V100 model -> CCR ≈ 638, so
    # overlap_frac_modeled pinned at ~0.006 — arithmetically correct,
    # diagnostically useless; see DESIGN.md §15).  The smoke fit keeps
    # its tiny geometry for wall-time stability; the model is priced at
    # paper scale because it costs nothing (static planning, no tracing).
    tune_row = api.tune(
        "gpt2-paper", reduced=False, dp_workers=64,
        candidates=(("covap", {}),), interval=4,
        seq_len=1024, global_batch=512,
        bucket_bytes=25 * 1024 * 1024, max_buckets=128,
    )[0]
    kernel_rows = {name: (us, derived) for name, us, derived in all_rows
                   if name.startswith("kernel/pack")}
    pack_us = kernel_rows.get("kernel/pack_fused", (None, ""))[0]
    m = re.search(r"speedup_fused=([\d.]+)",
                  kernel_rows.get("kernel/pack_unfused", (0, ""))[1])
    # sharded-sync gate results (benchmarks/sharded_check.py): the
    # schedule-level exposed-bytes ratio vs all-reduce and the compiled
    # placement counts, recorded alongside the existing fields
    sharded_rows = {name: derived for name, _, derived in all_rows
                    if name.startswith("sharded/")}
    ms = re.search(r"ratio=([\d.]+)",
                   sharded_rows.get("sharded/exposed_ratio", ""))
    mp = re.search(r"rs_before_final_grad=(\d+)",
                   sharded_rows.get("sharded/placement", ""))
    # hierarchical gate (benchmarks/hier_check.py): the DCN share of the
    # exposed wire bytes over one full phase cycle of the two-level plan
    hier_rows = {name: derived for name, _, derived in all_rows
                 if name.startswith("hier/")}
    mh = re.search(r"ratio=([\d.]+)",
                   hier_rows.get("hier/exposed_dcn_ratio", ""))
    # serving gate (benchmarks/serve_bench.py): per-stage unit costs and
    # the latency/throughput digest at the sweep's heaviest arrival rate
    serve_us = {name: us for name, us, _ in all_rows
                if name.startswith("serve/")}
    serve_derived = {name: derived for name, _, derived in all_rows
                     if name.startswith("serve/")}
    mt = re.search(r"tokens_per_s=([\d.]+)",
                   serve_derived.get("serve/tokens_per_s", ""))
    # telemetry-overhead gate result (benchmarks/obs_check.py)
    obs_us = {name: us for name, us, _ in all_rows
              if name.startswith("obs/")}
    # guard-overhead gate result (benchmarks/chaos_check.py)
    chaos_us = {name: us for name, us, _ in all_rows
                if name.startswith("chaos/")}

    def _serve(key, scale=1.0):
        v = serve_us.get(key)
        return v * scale if v is not None else None

    reg = MetricsRegistry()

    def g(name, value, help=""):
        reg.gauge(name, help).set(value)

    g("step_wall_s", wall_off, "min-of-3 amortised step wall, arena off")
    g("step_wall_s_arena", wall_on, "min-of-3 amortised step wall, arena on")
    g("bytes_per_worker_per_step", report["mean_bytes_per_step"],
      "static plan: mean collective bytes per worker per step")
    g("volume_ratio", report["volume_ratio"],
      "dense bytes / compressed bytes (static plan)")
    g("overlap_frac_modeled", tune_row["overlap_frac_modeled"],
      "eq-(6) overlap fraction at paper scale (seq1024 gb512 W=64)")
    g("pack_overhead_us_modeled", tune_row["pack_overhead_us"],
      "modeled arena pack-pass cost per phase, paper scale")
    g("pack_kernel_us", pack_us, "measured fused pack/EF/cast kernel wall")
    g("pack_fused_speedup", float(m.group(1)) if m else None,
      "fused pack kernel speedup over the 3-op unfused reference")
    g("sharded_exposed_ratio", float(ms.group(1)) if ms else None,
      "sharded-sync exposed wire bytes / all-reduce wire bytes")
    g("sharded_rs_before_final_grad",
      int(mp.group(1)) if mp else None,
      "compiled reduce-scatters placed before the final grad fusion")
    g("hier_exposed_dcn_ratio", float(mh.group(1)) if mh else None,
      "DCN share of exposed wire bytes in the two-level hierarchical plan")
    g("prefill_tok_us", _serve("serve/prefill_tok_us"),
      "serving prefill unit cost")
    g("generate_tok_us", _serve("serve/generate_tok_us"),
      "serving decode unit cost")
    g("insert_us", _serve("serve/insert_us"), "serving KV-insert unit cost")
    g("serve_p50_ms", _serve("serve/p50_ms", 1e-3),
      "traffic p50 latency at the heaviest swept rate")
    g("serve_p99_ms", _serve("serve/p99_ms", 1e-3),
      "traffic p99 latency at the heaviest swept rate")
    g("serve_ttft_ms", _serve("serve/ttft_ms", 1e-3),
      "traffic p50 time-to-first-token at the heaviest swept rate")
    g("serve_tokens_per_s", float(mt.group(1)) if mt else None,
      "sustained generated tokens/s at the heaviest swept rate")
    g("telemetry_overhead_frac", obs_us.get("obs/overhead_frac"),
      "instrumented/uninstrumented step-wall delta (obs_check gate)")
    g("guard_overhead_frac", chaos_us.get("chaos/guard_overhead_frac"),
      "guarded/unguarded step-wall delta (chaos_check gate)")
    return {
        "schema": 3,
        "unix_time": int(time.time()),
        "workload": "gpt2-paper/reduced covap I=4 seq32 gb8",
        **reg.snapshot(),
    }


# keys the trajectory gate watches: stable-by-construction measurements
# (min-of-trials walls, per-stage serving unit costs, latencies).  Modeled
# /analytic keys (bytes, ratios) change only when the code means them to,
# so a drift there should fail loudly too — but they are exact, not noisy,
# and are covered by their own module gates.  pack_kernel_us graduated to
# gated once kernel_bench moved to min-of-21 interleaved trials: the
# single-shot number drifted 166->205->269 across snapshots on unchanged
# kernel code, but the deep-min is reproducible well inside the 25%
# tolerance.  serve_ttft_ms is gated from the first snapshot that records
# it (keys absent from the previous snapshot are skipped, so its first
# appearance does not trip the gate).  Direction says which way is a
# regression.
TRAJECTORY_KEYS = {
    "step_wall_s": "lower",
    "step_wall_s_arena": "lower",
    "pack_kernel_us": "lower",
    "prefill_tok_us": "lower",
    "generate_tok_us": "lower",
    "insert_us": "lower",
    "serve_p50_ms": "lower",
    "serve_p99_ms": "lower",
    "serve_ttft_ms": "lower",
    "serve_tokens_per_s": "higher",
    "hier_exposed_dcn_ratio": "lower",
}
TRAJECTORY_TOLERANCE = 1.25  # >25% the wrong way = regression


def trajectory_regressions(prev: dict, new: dict) -> list[tuple]:
    """Compare two snapshots on the stable keys; returns the regressions
    as (key, prev, new) tuples.  Keys absent from either side are skipped
    (older snapshots predate the serving metrics)."""
    out = []
    for key, direction in TRAJECTORY_KEYS.items():
        a, b = prev.get(key), new.get(key)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
            continue
        if a <= 0 or b <= 0:
            continue
        ratio = (b / a) if direction == "lower" else (a / b)
        if ratio > TRAJECTORY_TOLERANCE:
            out.append((key, a, b))
    return out


def gate_against_prev(prev: dict, new: dict) -> list[tuple]:
    """Trajectory gate entry point: compares like-for-like only.  When the
    ``workload`` field differs between the snapshots every gated number
    measures a different thing — comparing them would flag phantom
    regressions (or mask real ones) — so the gate SKIPS with a printed
    notice instead of diffing apples against oranges."""
    pw, nw = prev.get("workload"), new.get("workload")
    if pw != nw:
        print(
            f"# trajectory gate SKIPPED: workload changed "
            f"({pw!r} -> {nw!r}); snapshots are not comparable",
            file=sys.stderr,
        )
        return []
    return trajectory_regressions(prev, new)


def write_snapshot(all_rows: list[tuple]) -> tuple[str, list[tuple]]:
    """Write BENCH_<n>.json and gate it against BENCH_<n-1>.  Returns the
    path and any trajectory regressions (caller decides to fail).  Set
    REPRO_BENCH_NO_TRAJECTORY_GATE=1 to record without gating (e.g. when a
    regression is understood and accepted)."""
    existing = glob.glob(os.path.join(_REPO_ROOT, "BENCH_*.json"))
    nums = sorted(
        int(m.group(1))
        for p in existing
        if (m := re.match(r"BENCH_(\d+)\.json$", os.path.basename(p)))
    )
    snap = build_snapshot(all_rows)
    path = os.path.join(_REPO_ROOT, f"BENCH_{(nums[-1] if nums else -1) + 1}.json")
    with open(path, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
        f.write("\n")
    regressions: list[tuple] = []
    if nums and not os.environ.get("REPRO_BENCH_NO_TRAJECTORY_GATE"):
        prev_path = os.path.join(_REPO_ROOT, f"BENCH_{nums[-1]}.json")
        with open(prev_path) as f:
            prev = json.load(f)
        regressions = gate_against_prev(prev, snap)
    return path, regressions


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="fast analytic subset for CI")
    args = ap.parse_args()
    enable_compile_cache()
    if args.only:
        names = args.only.split(",")
    elif args.smoke:
        names = list(SMOKE_MODULES)
    else:
        names = list(MODULES)

    print("name,us_per_call,derived")
    ok = True
    all_rows: list[tuple] = []
    for name in names:
        mod = MODULES[name]
        t0 = time.perf_counter()
        try:
            kw = {}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kw["smoke"] = True
            rows = mod.run(**kw)
            emit(rows)
            all_rows += rows
            print(f"# {name}: {len(rows)} rows in "
                  f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)
        except Exception:
            ok = False
            print(f"# {name}: FAILED", file=sys.stderr)
            traceback.print_exc()
    if ok and args.smoke and not args.only:
        path, regressions = write_snapshot(all_rows)
        print(f"# snapshot: {path}", file=sys.stderr)
        for key, prev, new in regressions:
            print(f"# TRAJECTORY REGRESSION {key}: {prev:.6g} -> {new:.6g} "
                  f"(>{(TRAJECTORY_TOLERANCE - 1) * 100:.0f}%)",
                  file=sys.stderr)
        if regressions:
            ok = False
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
