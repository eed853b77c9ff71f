import os

# compile-only CPU tool: fake 512 host devices, never an accelerator
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) combination:
``jit(step).lower(**ShapeDtypeStructs).compile()`` against the production
mesh — 16x16 (one pod, 256 chips) and 2x16x16 (two pods, 512 chips) — then
record ``memory_analysis()``, ``cost_analysis()`` and the parsed collective
schedule into a JSON report consumed by EXPERIMENTS.md SSDry-run/SSRoofline.

No arrays are ever materialised: inputs are ShapeDtypeStructs; compilation
alone proves the sharding config is coherent (sharding mismatches, OOM at
compile and unsupported collectives all fail here).

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.core import build_plan, get_compressor
from repro.core.ccr import (
    HardwareSpec,
    analytic_ccr,
    select_interval,
)
from repro.launch import analytic_costs, hlo_analysis, shardings as sh
from repro.launch.mesh import (
    dp_axes as dp_axes_fn,
    make_production_mesh,
    make_slice_mesh,
)
from repro.models import build_model, count_params, long_context_variant, model_flops
from repro.optim import adamw
from repro.train.trainer import build_train_step

HW = HardwareSpec.v5e()


def auto_interval(cfg, mesh, dp) -> int:
    """COVAP's adaptive I = ceil(CCR) from the analytic profiler (SS III.B).

    Same rule as ``repro.api``'s ``interval='auto'``; the multi-pod mesh
    splits the sync into the two-level decomposition (DESIGN.md §17):
    a ring all-reduce of the shard inside the pod over the ICI, plus a
    cross-pod exchange over the DCN of only the 1/W_intra slice the intra
    ring already reduced — priced through per-link ``CollectiveCall``
    wire models, so this stays consistent with the trainer's static
    ``CommSchedule`` accounting.  The intra-pod DP world is derived from
    the dp axes themselves (any axis but 'pod'), not a hardcoded axis
    name.
    """
    from repro.core.schedule import CollectiveCall

    n_chips = 1
    for a in mesh.shape:
        n_chips *= mesh.shape[a]
    dp_world = 1
    for a in dp:
        dp_world *= mesh.shape[a]
    tokens = INPUT_SHAPES["train_4k"].global_batch * INPUT_SHAPES["train_4k"].seq_len
    n_active = count_params(cfg, active_only=True)
    flops_per_chip = 6.0 * n_active * tokens / n_chips
    grad_bytes = count_params(cfg) * jnp.dtype(cfg.param_dtype).itemsize
    # gradient sync happens per model-shard: each DP group syncs its shard
    model_world = n_chips // dp_world
    shard = grad_bytes / model_world
    t_comp = (2.0 / 3.0) * flops_per_chip / (HW.peak_flops * HW.mfu)
    if "pod" in dp:
        w_intra = 1
        for a in dp:
            if a != "pod":
                w_intra *= mesh.shape[a]
        calls = (
            CollectiveCall(
                "grad-shard", "all_reduce", cfg.param_dtype, int(shard),
                link="ici", world=w_intra,
            ),
            # the DCN carries only the 1/W_intra slice each worker owns
            # after the intra ring reduced it
            CollectiveCall(
                "pod-shard", "all_reduce", cfg.param_dtype,
                int(shard) // max(w_intra, 1),
                link="dcn", world=mesh.shape["pod"],
            ),
        )
        bw = {"ici": HW.ici_bw, "dcn": HW.dcn_bw}
        t_comm = sum(c.wire_bytes(0) / bw[c.link] for c in calls)
        return select_interval(t_comm / max(t_comp, 1e-12))
    return select_interval(analytic_ccr(
        step_flops_per_chip=flops_per_chip,
        grad_bytes=shard,
        dp_world=dp_world,
        hw=HW,
    ))


def _spec_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def lower_train(model, mesh, dp, compressor_name: str, interval: int, phase: int,
                pod_interval: int = 1, sync: str = "allreduce"):
    cfg = model.cfg
    params_sds = _spec_shapes(model)
    plan = build_plan(params_sds, interval=interval,
                      param_specs=sh.train_param_specs(model, mesh))
    opts = {"interval": interval} if compressor_name == "covap" else {}
    if sync != "allreduce":
        opts["sync"] = sync
    compressor = get_compressor(compressor_name, **opts)
    moment_dtype = "bfloat16" if cfg.param_dtype == "bfloat16" else None
    optimizer = adamw(1e-4, moment_dtype=moment_dtype)

    p_specs = sh.train_param_specs(model, mesh)
    opt_sds = jax.eval_shape(optimizer.init, params_sds)
    comp_sds = jax.eval_shape(
        lambda p: compressor.init_state(p, plan), params_sds
    )
    shape = INPUT_SHAPES["train_4k"]
    batch_sds = model.input_specs(shape)

    hier = pod_interval > 1 and "pod" in mesh.shape
    if hier:
        n_pods = mesh.shape["pod"]

        def podded(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct((n_pods,) + a.shape, a.dtype),
                tree,
            )

        def pod_spec(tree):
            return jax.tree.map(
                lambda s: P(*(("pod",) + tuple(s))),
                tree,
                is_leaf=lambda x: isinstance(x, P),
            )

        params_sds, opt_sds, comp_sds = map(podded, (params_sds, opt_sds, comp_sds))
        p_specs_in = pod_spec(p_specs)
        opt_specs_in = pod_spec(sh.opt_state_specs(
            jax.eval_shape(optimizer.init, _spec_shapes(model)), p_specs))
        comp_specs_in = pod_spec(sh.comp_state_specs(
            jax.eval_shape(
                lambda p: compressor.init_state(p, plan), _spec_shapes(model)
            ),
            _spec_shapes(model), p_specs))
    else:
        p_specs_in = p_specs
        opt_specs_in = sh.opt_state_specs(opt_sds, p_specs)
        comp_specs_in = sh.comp_state_specs(comp_sds, params_sds, p_specs)

    step_jit = build_train_step(
        model, optimizer, compressor, plan,
        phase=phase, mesh=mesh, dp_axes=dp,
        param_shardings={
            "params": p_specs_in,
            "opt": opt_specs_in,
            "comp": comp_specs_in,
            "batch": jax.tree.map(lambda _: P(tuple(dp)), batch_sds),
        },
        donate=False,
        pod_interval=pod_interval,
    )
    step_sds = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = step_jit.lower(params_sds, opt_sds, comp_sds, batch_sds, step_sds)
    # the static plan of this phase, exactly as compiled: build_train_step
    # attaches the CommSchedule it planned (with the correct sync world —
    # pod excluded in hierarchical mode), so the recorded bytes are the
    # ones the HLO below must agree with
    sched = step_jit.comm_schedule
    # per-link injected bytes of everything the compiled step body runs:
    # the grad-sync collectives (exposed), the head all-gather freshening
    # last step's deferred shards (sharded sync re-plans the same gather
    # every phase, so this schedule's deferred bytes equal the prev one's),
    # and the cross-pod reconcile if this phase selects pod buckets
    planned_by_link: dict[str, float] = {}

    def _acc(d):
        for l, v in d.items():
            planned_by_link[l] = planned_by_link.get(l, 0.0) + v

    _acc(sched.exposed_bytes_by_link())
    _acc(sched.deferred_bytes_by_link())
    pod_sched = getattr(step_jit, "pod_schedule", None)
    if pod_sched is not None:
        _acc(pod_sched.exposed_bytes_by_link())
    if not hier and "pod" in mesh.shape and "pod" in tuple(dp):
        # flat sync over a multislice mesh: every grad collective's replica
        # group spans the pod boundary, which the HLO classifier (and the
        # physical network) counts as DCN traffic — relabel the record to
        # match; the schedule itself keeps its link labels since flat plans
        # are priced against a single-bandwidth model elsewhere
        planned_by_link = {"dcn": sum(planned_by_link.values())}
    meta = {
        "plan_buckets": plan.num_buckets,
        "interval": interval,
        "phase": phase,
        "compressor": compressor_name,
        "sync": sync,
        "pod_interval": pod_interval,
        "comm_schedule": sched.summary(),
        "pod_schedule": pod_sched.summary() if pod_sched is not None else None,
        "planned_bytes_per_worker": sched.bytes_per_worker,
        "planned_bytes_by_link": planned_by_link,
    }
    return lowered, meta


def _pick_serve_specs(model, mesh, *, include_pod: bool, strategy: str):
    """Serve weight sharding strategy (SSPerf lever).

    'model_data' shards weights over every non-batch axis (max HBM headroom,
    but each matmul re-gathers its weights); 'model' keeps TP-only sharding
    (weights resident per data row — no weight gathers); 'auto' picks
    'model' when the TP shard fits comfortably (< 6 GB/chip)."""
    if strategy == "auto":
        p_bytes = count_params(model.cfg) * jnp.dtype(model.cfg.param_dtype).itemsize
        strategy = "model" if p_bytes / mesh.shape["model"] < 6e9 else "model_data"
    if strategy == "model":
        return sh.train_param_specs(model, mesh), strategy
    return (
        sh.serve_param_specs(model, mesh, include_pod_in_weights=include_pod),
        strategy,
    )


def lower_prefill(model, mesh, dp, shape, *, serve_weights: str = "auto"):
    params_sds = _spec_shapes(model)
    p_specs, strategy = _pick_serve_specs(
        model, mesh, include_pod=False, strategy=serve_weights
    )
    batch_sds = model.input_specs(shape)
    b_specs = sh.batch_specs(batch_sds, mesh, dp)
    fn = jax.jit(
        model.prefill,
        in_shardings=(sh.as_named(mesh, p_specs), sh.as_named(mesh, b_specs)),
    )
    return fn.lower(params_sds, batch_sds), {"serve_weights": strategy}


def lower_decode(model, mesh, dp, shape, *, serve_weights: str = "auto"):
    cfg = model.cfg
    B, S = shape.global_batch, shape.seq_len
    params_sds = _spec_shapes(model)
    include_pod = B == 1 and "pod" in mesh.shape
    p_specs, strategy = _pick_serve_specs(
        model, mesh, include_pod=include_pod, strategy=serve_weights
    )
    cache_sds = model.cache_specs(B, S)
    c_specs = sh.cache_specs_tree(cache_sds, cfg, mesh, dp, B)
    batch_sds = model.input_specs(shape)
    b_specs = sh.batch_specs(batch_sds, mesh, dp)
    fn = jax.jit(
        model.decode_step,
        in_shardings=(
            sh.as_named(mesh, p_specs),
            sh.as_named(mesh, c_specs),
            sh.as_named(mesh, b_specs),
        ),
    )
    return fn.lower(params_sds, cache_sds, batch_sds), {"serve_weights": strategy}


def _memory_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # backend may not support it
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(ma)[:500]
    return out


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    keep = {}
    for k, v in dict(ca).items():
        if k in ("flops", "transcendentals", "bytes accessed") or k.startswith(
            "bytes accessed"
        ):
            keep[k] = float(v)
    return keep


def run_one(arch: str, shape_name: str, multi_pod: bool, *,
            compressor: str = "covap", interval: int | None = None,
            phase: int = 0, serve_weights: str = "auto",
            kv_cache_dtype: str = "", pod_interval: int = 1,
            sync: str = "allreduce", n_slices: int = 0) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch)
    variant = "exact"
    if shape_name == "long_500k":
        new_cfg = long_context_variant(cfg)
        variant = "native" if new_cfg is cfg else "sliding_window"
        cfg = new_cfg
    if kv_cache_dtype:
        cfg = cfg.with_(kv_cache_dtype=kv_cache_dtype)
    model = build_model(cfg)
    if n_slices:
        # compile-only N-slice sweep (MaxText-multislice style): each slice
        # is one pod behind a DCN crossing; smaller per-slice grid so the
        # sweep fits the 512 fake-device budget
        mesh = make_slice_mesh(n_slices)
        dp = ("pod", "data") if n_slices > 1 else ("data",)
        mesh_desc = "x".join(str(mesh.shape[a]) for a in mesh.shape)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        dp = dp_axes_fn(multi_pod=multi_pod)
        mesh_desc = "2x16x16" if multi_pod else "16x16"
    n_devices = 1
    for a in mesh.shape:
        n_devices *= mesh.shape[a]

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_desc,
        "n_devices": n_devices,
        "kind": shape.kind,
        "variant": variant,
        "status": "ok",
    }
    t0 = time.perf_counter()
    try:
        if shape.kind == "train":
            if interval is None and compressor == "covap":
                interval = auto_interval(cfg, mesh, dp)
            lowered, meta = lower_train(
                model, mesh, dp, compressor, interval or 1, phase,
                pod_interval=pod_interval, sync=sync,
            )
        elif shape.kind == "prefill":
            lowered, meta = lower_prefill(
                model, mesh, dp, shape, serve_weights=serve_weights
            )
        else:
            lowered, meta = lower_decode(
                model, mesh, dp, shape, serve_weights=serve_weights
            )
        if kv_cache_dtype:
            rec["kv_cache_dtype"] = kv_cache_dtype
        rec.update(meta)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)
        rec["memory_analysis"] = _memory_analysis(compiled)
        rec["cost_analysis_hlo"] = _cost_analysis(compiled)
        hlo = compiled.as_text()
        rec["collectives"] = hlo_analysis.collective_summary(hlo, trip_aware=True)
        rec["collectives_raw"] = hlo_analysis.collective_summary(
            hlo, trip_aware=False
        )

        # per-link cross-check (DESIGN.md §17): the statically planned
        # CommSchedule bytes vs the bytes the compiled HLO actually moves
        # over each link.  Plan numels are global while the HLO operates on
        # per-model-shard buffers, so the HLO side is scaled back up by the
        # model world before comparing.  Recorded, not asserted — the hard
        # gate is launch.hier_gate on an unsharded-model mesh.
        planned = rec.get("planned_bytes_by_link")
        if shape.kind == "train" and planned:
            n_pods_mesh = mesh.shape.get("pod", 1)
            hlo_by_link = hlo_analysis.collective_bytes_by_link(
                hlo,
                intra_world=n_devices // n_pods_mesh,
                min_bytes=2048,
                world=n_devices,
            )
            mw = mesh.shape.get("model", 1)
            scaled = {l: v * mw for l, v in hlo_by_link.items()}
            rel = {}
            for l in set(planned) | set(scaled):
                p, h = planned.get(l, 0.0), scaled.get(l, 0.0)
                # None, not inf: HLO traffic on a link with zero planned
                # bytes (e.g. model-TP activation collectives on ici under
                # flat-over-pods sync) — keeps the record strict JSON
                rel[l] = abs(h - p) / p if p else (0.0 if h == 0.0 else None)
            rec["bytes_by_link_check"] = {
                "schedule": planned,
                "hlo": hlo_by_link,
                "hlo_model_scaled": scaled,
                "rel_err": rel,
            }

        # roofline terms (per device).  compute/memory terms are ANALYTIC
        # (XLA cost_analysis counts scan bodies once — see analytic_costs);
        # the collective term is HLO-parsed with while-trip multiplication.
        dp_world = 1
        for a in dp:
            dp_world *= mesh.shape[a]
        model_world = mesh.shape.get("model", 1)
        flops_global = analytic_costs.step_flops(cfg, shape)
        flops = flops_global / n_devices
        extra = 1
        if shape.kind != "train" and shape.global_batch == 1 and "pod" in mesh.shape:
            extra = mesh.shape["pod"]
        hbm = analytic_costs.step_hbm_bytes(
            cfg, shape,
            model_shard=model_world,
            data_shard=dp_world,
            weight_shard_extra=extra,
        )
        wire = rec["collectives"]["wire_bytes_est"]
        terms = hlo_analysis.roofline_terms(
            flops_per_device=flops,
            hbm_bytes_per_device=hbm,
            wire_bytes_per_device=wire,
            peak_flops=HW.peak_flops, hbm_bw=HW.hbm_bw, ici_bw=HW.ici_bw,
        )
        tokens = (
            shape.global_batch
            if shape.kind == "decode"
            else shape.global_batch * shape.seq_len
        )
        mf = model_flops(cfg, tokens, "train" if shape.kind == "train" else "serve")
        rec["roofline"] = {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "flops_per_device": flops,
            "hbm_bytes_per_device": hbm,
            "wire_bytes_per_device": wire,
            "model_flops_global": mf,
            "model_flops_per_device": mf / n_devices,
            "useful_flops_ratio": mf / flops_global if flops_global else None,
        }
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--compressor", default="covap")
    ap.add_argument("--interval", type=int, default=None)
    ap.add_argument("--phase", type=int, default=0)
    ap.add_argument("--serve-weights", default="auto",
                    choices=["auto", "model", "model_data"])
    ap.add_argument("--kv-cache-dtype", default="")
    ap.add_argument("--pod-interval", type=int, default=1)
    ap.add_argument("--sync", default="allreduce", choices=["allreduce", "sharded"])
    ap.add_argument("--slices", default="",
                    help="comma list of slice counts for the multislice sweep "
                         "(e.g. 1,2,4); overrides --mesh with N-slice "
                         "(pod, 8, 8) compile-only meshes")
    ap.add_argument("--tag", default="", help="suffix for the output JSON")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list_archs(assigned_only=True) if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    if args.slices:
        # the multislice sweep reuses the mesh loop: one entry per N
        meshes = [int(s) for s in args.slices.split(",")]
        mesh_tags = [f"slice{n}" for n in meshes]
        slice_mode = True
    else:
        meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]
        mesh_tags = ["pod2" if m else "pod1" for m in meshes]
        slice_mode = False

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            for mesh_sel, mesh_tag in zip(meshes, mesh_tags):
                tag = f"{arch}__{shape}__{mesh_tag}__{args.compressor}"
                if args.sync != "allreduce":
                    tag += f"__{args.sync}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"skip {tag}")
                    continue
                rec = run_one(
                    arch, shape,
                    mesh_sel if not slice_mode else False,
                    compressor=args.compressor,
                    interval=args.interval, phase=args.phase,
                    serve_weights=args.serve_weights,
                    kv_cache_dtype=args.kv_cache_dtype,
                    pod_interval=args.pod_interval,
                    sync=args.sync,
                    n_slices=mesh_sel if slice_mode else 0,
                )
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(
                        f"OK   {tag:60s} compile={rec['compile_s']:7.1f}s "
                        f"dom={r['dominant']:10s} "
                        f"comp={r['compute_s']*1e3:8.2f}ms "
                        f"mem={r['memory_s']*1e3:8.2f}ms "
                        f"coll={r['collective_s']*1e3:8.2f}ms"
                    )
                else:
                    print(f"FAIL {tag:60s} {rec['error'][:120]}")


if __name__ == "__main__":
    main()
