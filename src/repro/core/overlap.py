"""Overlap execution engine: per-bucket collectives issued *inside* the
backward pass (the paper's Fig. 1(d) mechanism, executed rather than
simulated).

The post-hoc path (``SyncPipeline.execute``) runs every collective after
``value_and_grad`` returns, so compiled HLO serialises the whole exchange
behind the whole backward pass and overlap exists only in the perf model's
analytic timeline.  This module closes that gap:

* every bucket's parameter segments are routed through a ``jax.custom_vjp``
  **identity hook** at the top of the forward graph;
* the hook's backward rule receives exactly that bucket's gradient slices —
  which happens at the point of the backward trace where the bucket's last
  gradient is produced (``bucketing.ReadyOrder``'s reverse-topological
  readiness, realised structurally) — and calls the pipeline's granular
  :meth:`~repro.core.stages.SyncPipeline.execute_bucket` there, so the
  bucket's all-reduce enters the graph *before* the remaining backward
  compute and XLA's latency-hiding scheduler is free to interleave them;
* error feedback stays correct under hook-order execution: the residual is
  threaded in as a *differentiated input* whose only use is the hooks, so
  the cotangent JAX accumulates for it IS the new residual (selected
  buckets contribute the wire residual, unselected buckets the compensated
  gradient ``t``), bit-for-bit what the post-hoc path computes.

``launch.hlo_analysis.check_interleaving`` proves the mechanism on compiled
modules: with the hooks, at least one bucket collective is structurally
independent of the backward scan's while loop; post-hoc, none is.

With the zero-copy arena on (``use_arena`` compressor option /
``TrainConfig.arena``, DESIGN.md §12), the hook's backward sources its
payload from the bucket's contiguous arena slot instead of per-segment
collectives: ``execute_bucket`` packs the slices with the fused
``pack_ef_cast`` pass (EF compensation + wire cast + placement in one
sweep), issues ONE collective over the static slot view, and splits the
result with static slices — same bits, fewer copies, one collective per
bucket.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from . import bucketing as bk
from .schedule import CommSchedule
from .stages import SyncPipeline, _state_present


def supports_fused_overlap(compressor) -> bool:
    """Fused overlap needs bucket granularity and a segmented wire stage
    (COVAP / dense / fp16-cast): the hook's backward must be able to sync a
    bucket from its raw gradient slices alone.  Flat sparsifiers
    (value+index exchanges) and leaf-granularity schemes stay on the
    post-hoc path."""
    return (
        isinstance(compressor, SyncPipeline)
        and getattr(compressor, "granularity", "bucket") == "bucket"
        and getattr(compressor.wire, "segmented", False)
    )


def supports_sharded_sync(compressor) -> bool:
    """Sharded sync (reduce-scatter + deferred param all-gather, DESIGN.md
    §13) has the same structural requirement as fused overlap: a segmented
    bucket pipeline whose wire payload is a dense slot view the collective
    can partition evenly.  Value+index exchanges (top-k / sign / fp8
    gathers) and leaf-granularity schemes have no W-divisible dense buffer
    to scatter and stay on ``sync="allreduce"``."""
    return supports_fused_overlap(compressor)


def sharded_param_allgather(
    pipeline: SyncPipeline,
    schedule: CommSchedule,
    params: Any,
    *,
    axis_names: Sequence[str] = (),
) -> Any:
    """The deferred half of sharded sync: freshen EVERY bucket's parameters
    from their owners' updated shards (``schedule.deferred_calls``).

    After a sharded step, worker ``w``'s parameters are authoritative only
    on the shards ``w`` owns: for buckets selected that phase the owner
    applied the reduce-scattered gradient, and for every other
    once-selected bucket the optimizer's moment decay still moved the
    params — correctly only where the moments themselves are
    authoritative, i.e. on the owned shard again.  So the gather covers
    the whole plan, exactly like ZeRO's per-step parameter all-gather,
    not just the previous phase's selected buckets.  (Before a bucket's
    first selection its moments are zero and every worker computes the
    identical zero update, which is why the full-coverage gather is
    correct from step 0 — it rebroadcasts values that already agree.)

    Each bucket's param segments are packed into its W-aligned slot
    (promoted bucket dtype — params go on the wire uncompressed), the
    locally-owned shard sliced out, the shards all-gathered
    (``comm.all_gather_tiled``), and the leaves rebuilt with
    ``arena.gather_leaves``.

    Issued at the HEAD of the step — before the forward pass touches any
    parameter — so XLA's latency-hiding scheduler can overlap the gathers
    with forward compute; that placement is what makes the AG half of the
    schedule's bytes *deferred* rather than exposed.  Identity with no
    axes (single worker).
    """
    from . import arena as ar
    from .comm import all_gather_tiled, flat_axis_index

    if not axis_names or schedule.plan is None:
        return params
    plan = schedule.plan
    W = 1
    for a in axis_names:
        W *= jax.lax.axis_size(a)
    layout = ar.build_layout(plan, align=W)
    treedef = jax.tree_util.tree_structure(params)
    leaves = jax.tree_util.tree_leaves(params)
    planes = ar.pack_leaves(layout, leaves)
    w_idx = flat_axis_index(axis_names)
    fresh_pieces = {}
    for b in range(plan.num_buckets):
        with jax.named_scope(f"covap_param_ag_bucket_{b}"):
            view = layout.bucket_view(planes, b)
            S = view.shape[0] // W
            shard = jax.lax.dynamic_slice_in_dim(view, w_idx * S, S)
            full = all_gather_tiled(shard, axis_names)
            fresh_pieces[b] = layout.unpack_bucket(b, full)
    out_leaves = ar.gather_leaves(
        plan, lambda b, si, seg: fresh_pieces[b][si], leaves
    )
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def _assert_full_coverage(plan: bk.BucketPlan) -> None:
    """Every leaf element must be owned by exactly one bucket segment —
    otherwise some gradient would bypass the hooks unsynced."""
    covered = [0] * len(plan.leaf_shapes)
    for bucket in plan.buckets:
        for seg in bucket.segments:
            covered[seg.leaf_idx] += seg.numel(plan.leaf_shapes[seg.leaf_idx])
    for li, (shape, got) in enumerate(zip(plan.leaf_shapes, covered)):
        import numpy as np

        want = int(np.prod(shape, dtype=np.int64)) if shape else 1
        if got != want:
            raise ValueError(
                f"bucket plan covers {got}/{want} elements of leaf "
                f"{plan.leaf_paths[li]} — cannot install gradient hooks"
            )


def _make_bucket_hook(
    pipeline: SyncPipeline,
    schedule: CommSchedule,
    b: int,
    *,
    ef_on: bool,
    axis_names: Sequence[str],
):
    """A custom_vjp identity over one bucket's segment slices whose backward
    performs that bucket's synchronisation.

    Signature: ``hook(xs, rs, coeff) -> xs`` where ``xs`` are the param
    slices, ``rs`` the residual slices (``()`` without EF) and ``coeff`` the
    compensation coefficient (dummy scalar without EF).  The backward
    returns the globally-synced gradient as the cotangent of ``xs`` and the
    new residual as the cotangent of ``rs``.
    """

    @jax.custom_vjp
    def hook(xs, rs, coeff):
        return xs

    def fwd(xs, rs, coeff):
        return xs, (rs, coeff)

    def bwd(res, g_xs):
        rs, coeff = res
        # named_scope is metadata-only (no ops added, bits unchanged); it
        # labels this bucket's collective issue in XLA/Perfetto profiles
        # so comm attributes to buckets, not one anonymous backward blob.
        with jax.named_scope(f"covap_bucket_{b}/phase_{schedule.phase}"):
            synced, resids = pipeline.execute_bucket(
                schedule, b,
                list(g_xs),
                list(rs) if ef_on else None,
                coeff=coeff if ef_on else None,
                axis_names=axis_names,
            )
        if synced is None:  # unselected bucket: nothing crosses the wire
            g_cot = tuple(jnp.zeros_like(g) for g in g_xs)
        else:
            g_cot = tuple(
                x.astype(g.dtype) for x, g in zip(synced, g_xs)
            )
        if ef_on:
            r_cot = tuple(
                rr.astype(r.dtype) for rr, r in zip(resids, rs)
            )
        else:
            r_cot = ()
        return g_cot, r_cot, jnp.zeros_like(coeff)

    hook.defvjp(fwd, bwd)
    return hook


def install_hooks(
    pipeline: SyncPipeline,
    schedule: CommSchedule,
    params: Any,
    residual: Any,
    coeff,
    *,
    axis_names: Sequence[str] = (),
) -> Any:
    """Rebuild ``params`` with every bucket's segments routed through its
    gradient-ready hook.  Forward values are bitwise-identical (pure data
    movement); backward cotangents become the synced gradients."""
    plan = schedule.plan
    _assert_full_coverage(plan)
    ef_on = residual is not None
    treedef = jax.tree_util.tree_structure(params)
    leaves = jax.tree_util.tree_leaves(params)
    r_leaves = jax.tree_util.tree_leaves(residual) if ef_on else None
    coeff_arr = (
        jnp.asarray(coeff, jnp.float32) if ef_on else jnp.float32(0.0)
    )
    out = list(leaves)
    for bucket in plan.buckets:
        segs = bucket.segments
        xs = tuple(bk._slice_segment(leaves[s.leaf_idx], s) for s in segs)
        rs = (
            tuple(bk._slice_segment(r_leaves[s.leaf_idx], s) for s in segs)
            if ef_on else ()
        )
        hook = _make_bucket_hook(
            pipeline, schedule, bucket.index,
            ef_on=ef_on, axis_names=axis_names,
        )
        ys = hook(xs, rs, coeff_arr)
        for s, y in zip(segs, ys):
            out[s.leaf_idx] = bk._update_segment(out[s.leaf_idx], s, y)
    return jax.tree_util.tree_unflatten(treedef, out)


def overlapped_loss_and_grads(
    model,
    pipeline: SyncPipeline,
    schedule: CommSchedule,
    params: Any,
    comp_state: Any,
    batch: Any,
    step,
    *,
    axis_names: Sequence[str] = (),
):
    """The fused train-step core: one ``value_and_grad`` whose backward
    trace contains the phase's collectives at their readiness points.

    Returns ``(loss, metrics, synced_grads, new_comp_state)`` — the same
    contract as ``_loss_and_grads`` + ``pipeline.execute``, bit-for-bit.

    The EF residual rides along as a second differentiated argument: it
    never affects the loss (the hooks are identities on the params), so the
    gradient JAX computes for it is exactly the sum of the per-bucket
    residual cotangents — the new residual tree.
    """
    if not supports_fused_overlap(pipeline):
        raise ValueError(
            f"fused overlap supports segmented bucket pipelines "
            f"(COVAP/dense/wire-cast); got {pipeline!r} — use overlap='post'"
        )
    ef_on = pipeline.ef is not None and _state_present(comp_state)
    coeff = pipeline.ef_coefficient(step) if ef_on else None

    if ef_on:

        def lf(p, r):
            hooked = install_hooks(
                pipeline, schedule, p, r, coeff, axis_names=axis_names
            )
            with jax.named_scope("model"):
                return model.loss_fn(hooked, batch)

        (loss, metrics), (synced, new_r) = jax.value_and_grad(
            lf, argnums=(0, 1), has_aux=True
        )(params, comp_state)
        return loss, metrics, synced, new_r

    def lf0(p):
        hooked = install_hooks(
            pipeline, schedule, p, None, None, axis_names=axis_names
        )
        with jax.named_scope("model"):
            return model.loss_fn(hooked, batch)

    (loss, metrics), synced = jax.value_and_grad(lf0, has_aux=True)(params)
    return loss, metrics, synced, comp_state


__all__ = [
    "install_hooks",
    "overlapped_loss_and_grads",
    "sharded_param_allgather",
    "supports_fused_overlap",
    "supports_sharded_sync",
]
