"""End-to-end training driver.

    python -m repro.launch.train --arch gpt2-paper --compressor covap \
        --steps 200 --seq-len 128 --global-batch 8 --interval auto

Runs a real training loop on the local backend (CPU here; the same builder
serves the production mesh via --mesh), with COVAP's measured-CCR interval
selection, metric logging, and checkpointing.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.api import resolve_interval
from repro.configs import get_config, get_reduced
from repro.data import DataConfig, make_loader
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import adamw, cosine_warmup, sgd
from repro.train.trainer import TrainConfig, Trainer


def pick_interval(args, cfg) -> int:
    """``repro.api``'s adaptive rule: I = ceil(analytic_ccr) (paper SS III.B),
    modelled on the paper's environment (30 Gbps cloud) for CPU-local runs."""
    choice = resolve_interval(
        args.interval, cfg,
        global_batch=args.global_batch, seq_len=args.seq_len,
        dp_world=max(args.dp_workers, 1),
    )
    if choice.auto:
        print(f"[ccr] analytic CCR={choice.ccr:.2f} -> interval I={choice.interval}")
    return choice.interval


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-paper")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test REDUCED variant")
    ap.add_argument("--compressor", default="covap")
    ap.add_argument("--interval", default="auto")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--dp-workers", type=int, default=8,
                    help="modelled DP world size for CCR selection")
    ap.add_argument("--optimizer", default="adam", choices=["adam", "sgd"])
    ap.add_argument("--lr", type=float, default=1.5e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(params, optimizer AND error-feedback state)")
    ap.add_argument("--overlap", default="post", choices=["post", "fused"],
                    help="gradient-sync placement: post-backward (default) "
                         "or fused into the backward pass (overlap engine)")
    ap.add_argument("--adaptive", action="store_true",
                    help="arm the adaptive runtime: re-plan the interval "
                         "online from measured CCR")
    ap.add_argument("--arena", action="store_true",
                    help="zero-copy gradient arena: statically-planned "
                         "flat bucket buffers + fused pack/EF/cast pass "
                         "(bitwise-equal payloads, fewer copies)")
    ap.add_argument("--sync", default="allreduce",
                    choices=["allreduce", "sharded"],
                    help="collective decomposition: all-reduce per bucket "
                         "(default) or reduce-scatter + deferred param "
                         "all-gather at the next step's head (sharded "
                         "optimizer step; halves the exposed wire volume)")
    ap.add_argument("--guards", action="store_true",
                    help="arm the resilience runtime (repro.resilience): "
                         "numeric guardrails on every step + the skip-step "
                         "-> EF-flush -> checkpoint-rewind recovery ladder "
                         "(rewind needs --ckpt-dir/--ckpt-every)")
    ap.add_argument("--inject-faults", default="",
                    help="deterministic chaos schedule, e.g. "
                         "'grad_nan@10,ef_blowup@20x2,kill@30' "
                         "(kind@step[xTIMES][*SCALE]; implies --guards)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for fault-site selection (reproducible chaos)")
    ap.add_argument("--history-out", default="")
    ap.add_argument("--telemetry-dir", default="",
                    help="arm the unified telemetry subsystem (repro.obs): "
                         "writes events.jsonl (streamed), metrics.prom, "
                         "metrics.json and trace.json into this directory")
    args = ap.parse_args()
    enable_compile_cache()
    if args.interval == "adaptive":
        # mirror repro.api.fit: interval="adaptive" = analytic initial
        # pick + the online runtime armed
        args.adaptive = True

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    interval = pick_interval(args, cfg)

    if args.optimizer == "adam":
        opt = adamw(cosine_warmup(args.lr, args.steps // 10 + 1, args.steps))
    else:
        opt = sgd(args.lr, momentum=0.9)

    tc = TrainConfig(
        compressor=args.compressor, interval=interval,
        log_every=args.log_every, steps=args.steps,
        overlap=args.overlap, arena=args.arena, sync=args.sync,
    )
    tr = Trainer(model, opt, tc)
    print(f"[plan] {tr.plan.num_buckets} buckets, "
          f"target {tr.plan.bucket_bytes_target/1e6:.1f} MB, "
          f"{tr.num_phases} phase executable(s)")
    sr = tr.schedule_report()
    print(f"[schedule] mean {sr['mean_bytes_per_step']/1e6:.3f} MB/step "
          f"per worker (dense {sr['dense_bytes']/1e6:.3f} MB, "
          f"volume ratio {sr['volume_ratio']:.2f}x) — static plan, no tracing")
    if args.sync == "sharded":
        print(f"[schedule] sharded: "
              f"{sr['mean_exposed_wire_bytes_per_step']/1e6:.3f} MB/step "
              f"exposed wire (RS), "
              f"{sr['mean_deferred_bytes_per_step']/1e6:.3f} MB/step "
              f"deferred param AG riding the next forward pass")

    state = tr.init_state(jax.random.PRNGKey(0))
    if args.resume and args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir) is not None:
        state, extra = checkpoint.restore_train_state(args.ckpt_dir, state)
        print(f"[ckpt] resumed step {state['step']} "
              f"(EF state: {extra.get('has_comp_state')}, "
              f"saved interval: {extra.get('interval')})")
        if not extra.get("comp_restored", True):
            print("[ckpt] WARNING: saved compressor state is structurally "
                  "incompatible with this config (EF on/off changed); "
                  "residual re-initialised")
        elif extra.get("interval") not in (None, interval):
            # the residual was accumulated under a different cadence:
            # cross the boundary through the runtime's transition logic
            state, rep = tr.replan(interval, state, step=state["step"],
                                   old_interval=extra["interval"])
            print(f"[ckpt] interval {extra['interval']} -> {interval}: "
                  f"residual {rep.policy} "
                  f"(norm {rep.norm_before:.3e} -> {rep.norm_after:.3e})")
    n_params = sum(int(x.size) for x in jax.tree.leaves(state["params"]))
    print(f"[model] {cfg.name}: {n_params/1e6:.1f}M params")

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    global_batch=args.global_batch)
    loader = iter(make_loader(dc))

    autotune = None
    if args.adaptive:
        # one runtime for the whole run: chunked (checkpoint-every) calls
        # must not reset the controller's patience/cooldown or the trace
        from repro.runtime import AdaptiveRuntime

        autotune = AdaptiveRuntime(tr)
    resilience = None
    if args.guards or args.inject_faults:
        # one runtime across chunked run calls, like the AdaptiveRuntime:
        # the recovery ladder and fault firing counts must not reset at
        # checkpoint boundaries
        from repro.resilience import (
            GuardConfig, ResilienceRuntime, parse_fault_spec,
        )

        gcfg = GuardConfig(
            ckpt_dir=args.ckpt_dir or None,
            # the guard-owned rewind target rides the normal ckpt cadence
            ckpt_every=args.ckpt_every if args.ckpt_dir else 0,
        )
        plan = (
            parse_fault_spec(args.inject_faults, seed=args.fault_seed)
            if args.inject_faults else None
        )
        resilience = ResilienceRuntime(tr, guards=gcfg, faults=plan)
        msg = "guards armed (skip-step -> EF-flush -> rewind)"
        if plan is not None:
            msg += f"; injecting {len(plan.events)} fault(s): " \
                   f"{','.join(e.kind + '@' + str(e.step) for e in plan.events)}"
        print(f"[resilience] {msg}")
    telemetry = None
    if args.telemetry_dir:
        from repro.obs import Telemetry

        telemetry = Telemetry(args.telemetry_dir)
        print(f"[telemetry] streaming events to "
              f"{os.path.join(args.telemetry_dir, 'events.jsonl')}")
    t0 = time.perf_counter()
    done = 0
    while done < args.steps:
        chunk = args.steps - done
        if args.ckpt_dir and args.ckpt_every > 0:
            chunk = min(chunk, args.ckpt_every)
        state = tr.run(state, loader, steps=chunk, autotune=autotune,
                       telemetry=telemetry, guards=resilience)
        done += chunk
        if args.ckpt_dir and (args.ckpt_every > 0 or done >= args.steps):
            path = checkpoint.save_train_state(
                args.ckpt_dir, state, interval=tr.tc.interval,
            )
            print(f"[ckpt] saved {path} (params + opt + EF residuals)")
            if telemetry is not None:
                telemetry.events.emit(
                    "checkpoint", step=int(state["step"]), path=path
                )
    wall = time.perf_counter() - t0
    tokens = args.steps * args.global_batch * args.seq_len
    last = tr.history[-1]
    print(f"[done] {wall:.1f}s, {tokens/wall:.0f} tok/s, "
          f"final loss {last.get('loss', last['total_loss']):.4f}")
    if args.adaptive and tr.runtime is not None:
        s = tr.runtime.summary()
        print(f"[autotune] measured CCR "
              f"{(s['measured_ccr'] or 0.0):.3f}, interval {s['interval']}, "
              f"{s['replans']} re-plan(s)")
    if resilience is not None:
        rs = resilience.summary()
        print(f"[resilience] {rs['trips']} guard trip(s) "
              f"{rs['trips_by_guard']}, {rs['actions']} recovery action(s) "
              f"{rs['actions_by_rung']}"
              + (f", faults fired {rs['faults']['by_kind']}"
                 if "faults" in rs else ""))
    if args.history_out:
        os.makedirs(os.path.dirname(args.history_out) or ".", exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump({"config": vars(args), "interval": interval,
                       "history": tr.history}, f, indent=1)
        print(f"[history] {args.history_out}")
    if telemetry is not None:
        if args.adaptive and tr.runtime is not None:
            tr.runtime.finish()     # planned per-bucket spans -> trace
        paths = telemetry.save()
        telemetry.close()
        print(f"[telemetry] {paths['snapshot']}  {paths['prom']}  "
              f"{paths['trace']} (open in Perfetto)")


if __name__ == "__main__":
    main()
