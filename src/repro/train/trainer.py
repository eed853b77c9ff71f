"""DP train-step builder: COVAP (or any registered GC scheme) wired into the
gradient synchronisation of a ``shard_map``-manual data-parallel step.

Key structural points (DESIGN.md SS2):

* ``shard_map`` is **manual over the DP axes** ('pod','data') so each
  worker's gradients exist un-reduced and the compressor controls exactly
  which bytes cross the interconnect (one ``psum`` per selected bucket);
  the 'model' axis stays **auto** so tensor-parallel sharding of the model
  math is compiler-managed.
* Plan/execute split (DESIGN.md SS3): each phase's ``CommSchedule`` is
  computed **outside** the traced function by ``Compressor.plan_phase`` —
  the trainer knows the exact planned collective bytes before (and without)
  compiling anything — and the pure ``Compressor.execute`` consumes it
  inside ``shard_map``.
* The coarse filter's bucket selection must be static in XLA, so the step
  is specialised per ``phase = step % I`` -> ``I`` executables, compiled
  lazily on first use.
* Loss/grad math is unchanged across compressors — swapping schemes swaps
  only the sync stage (the paper's DDP-communication-hook shape).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import build_plan, get_compressor
from repro.core.bucketing import BucketPlan
from repro.core.comm import Compressor, dense_bytes
from repro.core.filter import selected_buckets
from repro.core.schedule import CollectiveCall, CommSchedule, mean_bytes_per_step
from repro.optim import Optimizer, apply_updates, clip_by_global_norm, global_norm


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    compressor: str = "covap"
    compressor_options: dict = dataclasses.field(default_factory=dict)
    interval: int = 4                      # COVAP I = ceil(CCR); 1 = no filter
    pod_interval: int = 1                  # hierarchical COVAP across pods
    bucket_bytes: int = 25 * 1024 * 1024
    max_buckets: int = 128
    clip_norm: float = 0.0                 # 0 = off
    steps: int = 100
    log_every: int = 10
    # gradient-sync placement: "post" runs every collective after the full
    # backward pass (the classic path, pinned bit-for-bit); "fused" issues
    # each bucket's collective inside the backward trace via the overlap
    # engine's gradient-ready hooks (core/overlap.py) so XLA can interleave
    # comm with the remaining backward compute.  Segmented bucket pipelines
    # only (COVAP / none / fp16).
    overlap: str = "post"
    # zero-copy gradient arena (core/arena.py, DESIGN.md §12): bucket
    # payloads become static-offset views of per-phase flat planes — one
    # pack pass per step (fused EF + wire cast), one collective per bucket
    # over a contiguous slice, static-slice unpacks on the way back —
    # instead of per-bucket concatenate / dynamic_slice rebuilds.
    # Bitwise-equal to the default path for uniform-dtype models.
    arena: bool = False
    # collective decomposition (core/comm.py + DESIGN.md §13/§17):
    # "allreduce" all-reduces each selected bucket (the classic path,
    # pinned); "sharded" reduce-scatters the compressed slot view (each
    # worker keeps 1/W), lets the optimizer's meaningful updates land on
    # the local shard, and defers the all-gather of updated params to the
    # HEAD of the next step so it overlaps the forward pass — exposed wire
    # volume behind the backward pass drops to ~half of the all-reduce
    # path's.  Segmented bucket pipelines only (covap / none / fp16).
    # Composes with hierarchical pods (pod_interval > 1): the gradient RS
    # runs over the fast intra-pod axes, ``pod_reconcile`` exchanges only
    # the owned 1/W shard of each selected bucket across the DCN, and the
    # deferred head all-gather freshens non-owner shards from the pod's
    # owners (DESIGN.md §17).
    sync: str = "allreduce"


def make_compressor(tc: TrainConfig) -> Compressor:
    opts = dict(tc.compressor_options)
    if tc.compressor == "covap":
        opts.setdefault("interval", tc.interval)
    if tc.arena:
        opts.setdefault("use_arena", True)
    if tc.sync != "allreduce":
        opts.setdefault("sync", tc.sync)
    return get_compressor(tc.compressor, **opts)


def _loss_and_grads(model, params, batch):
    def lf(p):
        # the scope labels the forward ops ``jvp(model)`` and the backward
        # ops ``transpose(jvp(model))`` in the HLO metadata and the profile
        with jax.named_scope("model"):
            loss, metrics = model.loss_fn(p, batch)
        return loss, metrics

    (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
    return loss, metrics, grads


def strip_pod_block(tree, *, expect_local: bool = True):
    """Drop the leading per-pod block axis from every leaf of a
    hierarchical train state.

    Inside the shard_map the state is sharded ``P('pod')``, so the local
    block size must be exactly 1 — ``expect_local=True`` asserts that with
    a clear error instead of silently indexing.  Host-side callers (e.g.
    the CCR probe peeling pod 0 off a full ``(n_pods, ...)`` state) pass
    ``expect_local=False``.
    """

    def strip(a):
        if expect_local and a.shape[0] != 1:
            raise ValueError(
                f"hierarchical state leaf has local pod block size "
                f"{a.shape[0]}, expected 1 (shape {a.shape}); the state "
                f"must enter shard_map sharded P('pod')"
            )
        return a[0]

    return jax.tree.map(strip, tree)


def restore_pod_block(tree):
    """Re-attach the length-1 pod block axis removed by
    :func:`strip_pod_block` (inverse inside the shard_map body)."""
    return jax.tree.map(lambda a: a[None], tree)


def plan_pod_schedule(
    plan: BucketPlan, *, pod_phase: int, pod_interval: int,
    sync: str = "allreduce", intra_world: int = 1, n_pods: int = 1,
) -> CommSchedule:
    """Static cross-pod reconciliation plan (hierarchical COVAP, DESIGN
    SS7b + §17): the coarse filter's selection rule applied at the pod
    level.

    With ``intra_world <= 1`` (legacy flat accounting) each selected
    bucket is one f32 all-reduce of its full extent over the pod group.
    With ``intra_world = W > 1`` the plan is the two-level decomposition
    :func:`pod_reconcile` executes: per selected bucket a DCN all-reduce
    of only the owned ``1/W`` shard of the W-aligned slot (at the
    bucket's promoted dtype — what actually crosses the slow link), plus
    — under ``sync="allreduce"`` only — the intra-pod all-gather that
    rebuilds the full slot on the fast link.  Under ``sync="sharded"``
    the rebuild rides the next step's deferred head all-gather instead,
    so no ICI call is planned here."""
    from repro.core import arena as ar

    interval = max(int(pod_interval), 1)
    sel = selected_buckets(plan.num_buckets, pod_phase % interval, interval)
    W = max(int(intra_world), 1)
    pod_world = int(n_pods) if int(n_pods) > 1 else 0
    calls: list[CollectiveCall] = []
    if W <= 1:
        for b in sel:
            calls.append(CollectiveCall(
                f"pod-bucket:{b}", "all_reduce", "float32",
                plan.buckets[b].numel * 4, link="dcn", world=pod_world,
            ))
    else:
        for b in sel:
            bucket = plan.buckets[b]
            dt = ar.bucket_dtype(plan, bucket)
            shard_bytes = (
                ar.aligned_numel(bucket.numel, W) // W
            ) * dt.itemsize
            calls.append(CollectiveCall(
                f"pod-bucket:{b}", "all_reduce", dt.name, shard_bytes,
                link="dcn", world=pod_world,
            ))
            if sync == "allreduce":
                calls.append(CollectiveCall(
                    f"pod-ag:{b}", "all_gather", dt.name, shard_bytes,
                    link="ici", world=W,
                ))
    return CommSchedule(
        compressor="pod_reconcile",
        phase=pod_phase % interval,
        num_phases=interval,
        granularity="bucket",
        selected=sel,
        calls=tuple(calls),
        dense_bytes=sum(b.numel for b in plan.buckets) * 4,
        plan=plan,
    )


def pod_reconcile(params, schedule: CommSchedule, *,
                  pod_axes: Sequence[str],
                  reconcile_helper_axes: Sequence[str] = (),
                  owned_only: bool = False):
    """Hierarchical COVAP's cross-pod level (beyond-paper, DESIGN SS7b +
    §17): instead of sending every gradient across the slow DCN pod
    links, each step reconciles only the PARAMETER segments named by the
    static ``CommSchedule`` (buckets with ``(b + step) % I_pod == 0`` —
    the coarse filter applied at the pod level, where CCR > 1 genuinely
    holds).  Local-SGD-style drift between reconciliations, bounded to
    I_pod steps per bucket by the round-robin.

    The exchange is an EXPLICIT two-level decomposition over the
    ``reconcile_helper_axes`` (the intra-pod DP axes, W workers): each
    selected bucket is packed into its W-aligned arena slot, worker ``w``
    slices the shard ``[w*S, (w+1)*S)`` it owns — free, no collective;
    under allreduce sync params are intra-pod replicated so the slice is
    exact, under sharded sync it is precisely the shard the optimizer
    just updated — and :func:`~repro.core.comm.pod_shard_exchange`
    pmean-reconciles only that 1/W shard across the pods.  Only shard-
    sized payloads ever touch the DCN.  Then:

    * ``owned_only=False`` (allreduce sync): an intra-pod all-gather on
      the fast link rebuilds the full reconciled slot on every worker;
    * ``owned_only=True`` (sharded sync): the reconciled shard is written
      back to the owned region only — non-owner positions stay stale by
      contract and are freshened by the next step's deferred head
      all-gather, which always gathers from the shard owners.

    Returns (params, schedule.bytes_per_worker)."""
    from repro.core import arena as ar
    from repro.core import bucketing as bk
    from repro.core.comm import (
        all_gather_tiled, flat_axis_index, pod_shard_exchange,
    )

    plan = schedule.plan
    treedef = jax.tree_util.tree_structure(params)
    leaves = jax.tree_util.tree_leaves(params)
    helper = tuple(reconcile_helper_axes)
    W = 1
    for a in helper:
        W *= lax.axis_size(a)
    if not schedule.selected:
        return params, schedule.bytes_per_worker
    layout = ar.build_layout(plan, schedule.selected, align=W)
    planes = ar.pack_leaves(layout, leaves)
    for b in schedule.selected:
        view = layout.bucket_view(planes, b)
        if W > 1:
            S = view.shape[0] // W
            w = flat_axis_index(helper)
            shard = lax.dynamic_slice_in_dim(view, w * S, S)
            shard = pod_shard_exchange(shard, pod_axes)
            if owned_only:
                full = lax.dynamic_update_slice(view, shard, (w * S,))
            else:
                full = all_gather_tiled(shard, helper)
        else:
            full = pod_shard_exchange(view, pod_axes)
        for seg, piece in zip(
            plan.buckets[b].segments, layout.unpack_bucket(b, full)
        ):
            li = seg.leaf_idx
            leaves[li] = bk._update_segment(
                leaves[li], seg, piece.astype(leaves[li].dtype)
            )
    return (
        jax.tree_util.tree_unflatten(treedef, leaves),
        schedule.bytes_per_worker,
    )


def build_step_fn(
    model,
    optimizer: Optimizer,
    compressor: Compressor,
    plan: BucketPlan,
    *,
    phase: int,
    dp_axes: Sequence[str] = (),
    clip_norm: float = 0.0,
    pod_interval: int = 1,
    dp_world: int = 1,
    n_pods: int = 1,
) -> Callable:
    """The un-jitted per-phase step (runs inside shard_map when dp_axes).

    The phase's ``CommSchedule`` is planned here, statically — the traced
    body only ever sees ``compressor.execute(schedule, ...)``.

    With ``pod_interval > 1`` (hierarchical mode) gradient sync runs only
    over the intra-pod axes; the 'pod' axis is reconciled by
    ``pod_reconcile`` and the state carries a leading pod-block axis.

    Sharded sync compressors additionally issue the deferred param
    all-gather at the step's head (see :func:`_build_phase_step`)."""
    return _build_phase_step(
        model, optimizer, compressor, plan, phase=phase, dp_axes=dp_axes,
        clip_norm=clip_norm, pod_interval=pod_interval, dp_world=dp_world,
        fused=False, n_pods=n_pods,
    )


def build_overlapped_step(
    model,
    optimizer: Optimizer,
    compressor: Compressor,
    plan: BucketPlan,
    *,
    phase: int,
    dp_axes: Sequence[str] = (),
    clip_norm: float = 0.0,
    pod_interval: int = 1,
    dp_world: int = 1,
    n_pods: int = 1,
) -> Callable:
    """The fused-overlap per-phase step (``TrainConfig.overlap="fused"``).

    Identical contract to :func:`build_step_fn`, but gradient sync happens
    INSIDE the backward pass: every bucket's parameter segments are routed
    through a gradient-ready hook (``core.overlap``) whose backward rule
    issues that bucket's planned collective the moment its last gradient is
    produced — XLA's latency-hiding scheduler can then interleave each
    bucket's all-reduce with the remaining backward compute instead of
    serialising comm after compute.  Bit-for-bit equal to the post path
    (the hooks call the same granular ``execute_bucket``) on the pure-DP
    mesh; with hierarchical pods (``pod_interval > 1``) XLA's fusion
    choices may differ between the two compiled programs at the ulp level,
    so equivalence there is numerical (~1e-7), not bitwise.
    """
    from repro.core.overlap import supports_fused_overlap

    if not supports_fused_overlap(compressor):
        raise ValueError(
            f"overlap='fused' requires a segmented bucket pipeline "
            f"(covap / none / fp16); {compressor!r} must use overlap='post'"
        )
    return _build_phase_step(
        model, optimizer, compressor, plan, phase=phase, dp_axes=dp_axes,
        clip_norm=clip_norm, pod_interval=pod_interval, dp_world=dp_world,
        fused=True, n_pods=n_pods,
    )


def _sharded_grad_norm(synced, grad_axes):
    """Global gradient norm under sharded sync: each worker's ``synced``
    tree is zero off its owned shards, so the exact global square-sum is
    the psum of the local ones (summation order differs from the allreduce
    path's single-array norm, so the metric agrees to ~ulp, not bitwise)."""
    sq = sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(synced)
    )
    if grad_axes:
        sq = lax.psum(sq, tuple(grad_axes))
    return jnp.sqrt(sq)


def _build_phase_step(
    model, optimizer, compressor, plan, *, phase, dp_axes, clip_norm,
    pod_interval, dp_world, fused, n_pods=1,
) -> Callable:
    """Shared skeleton of :func:`build_step_fn` / :func:`build_overlapped_step`
    — only the loss/grads/sync block differs; each path keeps its exact
    traced op order (the post path is pinned bit-for-bit).

    Sharded sync (``compressor.sync_mode == "sharded"``): every step begins
    with the deferred param all-gather of the PREVIOUS step
    (``overlap.sharded_param_allgather``) — the previous optimizer step
    landed authoritative values only on locally-owned shards, and the head
    gather freshens all of them before the forward pass touches any
    parameter, so the AG overlaps forward compute instead of extending the
    previous step's sync tail.  The gather is phase-independent (it covers
    every bucket) and is an identity on already-fresh params, so it runs
    unconditionally (step 0 included)."""
    pod_axes = tuple(a for a in dp_axes if a == "pod") if pod_interval > 1 else ()
    grad_axes = tuple(a for a in dp_axes if a not in pod_axes)
    sharded = getattr(compressor, "sync_mode", "allreduce") == "sharded"

    comm_schedule = compressor.plan_phase(plan, phase, world=dp_world)
    prev_schedule = comm_schedule if sharded and grad_axes else None
    pod_schedule = (
        plan_pod_schedule(
            plan, pod_phase=phase % pod_interval, pod_interval=pod_interval,
            sync="sharded" if sharded else "allreduce",
            intra_world=dp_world, n_pods=n_pods,
        )
        if pod_axes
        else None
    )

    def pmean_metrics(loss, metrics):
        if not dp_axes:
            return loss, metrics
        return (
            lax.pmean(loss, tuple(dp_axes)),
            jax.tree.map(lambda m: lax.pmean(m, tuple(dp_axes)), metrics),
        )

    def step_fn(params, opt_state, comp_state, batch, step):
        hier = bool(pod_axes)
        if hier:
            params, opt_state, comp_state = strip_pod_block(
                (params, opt_state, comp_state)
            )
        if prev_schedule is not None:
            from repro.core.overlap import sharded_param_allgather

            params = sharded_param_allgather(
                compressor, prev_schedule, params, axis_names=grad_axes,
            )
        if fused:
            from repro.core.overlap import overlapped_loss_and_grads

            loss, metrics, synced, comp_state = overlapped_loss_and_grads(
                model, compressor, comm_schedule,
                params, comp_state, batch, step, axis_names=grad_axes,
            )
            loss, metrics = pmean_metrics(loss, metrics)
        else:
            loss, metrics, grads = _loss_and_grads(model, params, batch)
            loss, metrics = pmean_metrics(loss, metrics)
            synced, comp_state, _ = compressor.execute(
                comm_schedule, grads, comp_state,
                step=step, axis_names=grad_axes,
            )
        with jax.named_scope("optimizer"):
            if sharded and grad_axes:
                gnorm = _sharded_grad_norm(synced, grad_axes)
                if clip_norm > 0:
                    scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-12))
                    synced = jax.tree.map(
                        lambda x: (
                            x.astype(jnp.float32) * scale
                        ).astype(x.dtype),
                        synced,
                    )
            elif clip_norm > 0:
                synced, gnorm = clip_by_global_norm(synced, clip_norm)
            else:
                gnorm = global_norm(synced)
            updates, opt_state = optimizer.update(synced, opt_state, params)
            params = apply_updates(params, updates)
        if hier:
            params, _ = pod_reconcile(
                params, pod_schedule,
                pod_axes=pod_axes, reconcile_helper_axes=grad_axes,
                owned_only=sharded,
            )
            params, opt_state, comp_state = restore_pod_block(
                (params, opt_state, comp_state)
            )
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["total_loss"] = loss
        return params, opt_state, comp_state, metrics

    step_fn.comm_schedule = comm_schedule
    step_fn.prev_schedule = prev_schedule
    step_fn.pod_schedule = pod_schedule
    return step_fn


def build_train_step(
    model,
    optimizer: Optimizer,
    compressor: Compressor,
    plan: BucketPlan,
    *,
    phase: int,
    mesh=None,
    dp_axes: Sequence[str] = (),
    param_shardings=None,
    clip_norm: float = 0.0,
    donate: bool = True,
    pod_interval: int = 1,
    overlap: str = "post",
):
    """jit (+ shard_map over DP axes) the per-phase step.

    Single-process CPU path: ``mesh=None`` -> plain jit, no collectives.
    Production path: manual over ``dp_axes``, auto over everything else.
    Hierarchical mode (``pod_interval > 1``): state carries a leading
    per-pod axis (P('pod')) so pods may drift between reconciliations.
    ``overlap``: "post" (sync after the backward pass, the pinned default)
    or "fused" (:func:`build_overlapped_step`'s in-backward collectives).
    """
    if overlap not in ("post", "fused"):
        raise ValueError(f"overlap must be 'post' or 'fused', got {overlap!r}")
    hier = pod_interval > 1 and "pod" in dp_axes
    # the compressor's collectives run over the gradient-sync axes only:
    # in hierarchical mode the 'pod' axis is reconciled separately, so the
    # schedule must be planned for the intra-pod world
    sync_axes = tuple(a for a in dp_axes if a != "pod") if hier else tuple(dp_axes)
    dp_world = 1
    if mesh is not None:
        for a in sync_axes:
            dp_world *= mesh.shape[a]
    n_pods = mesh.shape["pod"] if hier and mesh is not None else 1
    builder = build_overlapped_step if overlap == "fused" else build_step_fn
    step_fn = builder(
        model, optimizer, compressor, plan,
        phase=phase, dp_axes=dp_axes if mesh is not None else (),
        clip_norm=clip_norm, pod_interval=pod_interval if hier else 1,
        dp_world=dp_world, n_pods=n_pods,
    )
    if mesh is None:
        jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2) if donate else ())
        jitted.comm_schedule = step_fn.comm_schedule
        jitted.prev_schedule = step_fn.prev_schedule
        jitted.pod_schedule = step_fn.pod_schedule
        return jitted

    state_spec = P("pod") if hier else P()
    batch_spec = P(tuple(dp_axes))
    mapped = jax.shard_map(
        step_fn,
        mesh=mesh,
        in_specs=(
            state_spec,                           # params
            state_spec,                           # opt_state
            state_spec,                           # comp_state (residuals)
            batch_spec,                           # batch (sharded on dim 0)
            P(),                                  # step
        ),
        out_specs=(state_spec, state_spec, state_spec, P()),
        axis_names=set(dp_axes),
        check_vma=False,
    )
    kw = {}
    if param_shardings is not None:
        like = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        kw["in_shardings"] = (
            like(param_shardings["params"]),
            like(param_shardings["opt"]),
            like(param_shardings["comp"]),
            like(param_shardings["batch"]),
            NamedSharding(mesh, P()),
        )
    jitted = jax.jit(mapped, donate_argnums=(0, 1, 2) if donate else (), **kw)
    jitted.comm_schedule = step_fn.comm_schedule
    jitted.prev_schedule = step_fn.prev_schedule
    jitted.pod_schedule = step_fn.pod_schedule
    return jitted


def _donated_share(tree) -> float:
    """Share of ``tree``'s bytes whose buffers a donating call consumed
    (deleted).  Under 1 when some leaf could not alias an output and was
    kept ("Some donated buffers were not usable")."""
    leaves = jax.tree.leaves(tree)
    total = sum(x.nbytes for x in leaves)
    gone = sum(
        x.nbytes for x in leaves
        if isinstance(x, jax.Array) and x.is_deleted()
    )
    return gone / total if total else 1.0


@jax.jit
def _copy_arrays(tree):
    # one dispatch for the whole state; module scope, so it compiles once
    # per state structure rather than once per caller
    return jax.tree.map(jnp.copy, tree)


def copy_train_state(state: dict) -> dict:
    """``state`` with fresh device copies of its params, optimizer moments
    and EF residuals (the step counter is shared): what a caller keeps
    when it hands the original to :meth:`Trainer.run`, which consumes it."""
    keys = [k for k in ("params", "opt", "comp") if k in state]
    return {**state, **_copy_arrays({k: state[k] for k in keys})}


def make_train_state(model, optimizer, compressor, plan, key):
    params = model.init(key)
    return {
        "params": params,
        "opt": optimizer.init(params),
        "comp": compressor.init_state(params, plan),
        "step": 0,
    }


class Trainer:
    """Host loop: lazily compiles one executable per COVAP phase, logs
    metrics, exposes measured step timing for the CCR profiler and the
    static per-phase ``CommSchedule``s for byte/overlap accounting."""

    def __init__(self, model, optimizer, tc: TrainConfig, *, mesh=None,
                 dp_axes: Sequence[str] = (), param_specs=None):
        self.model = model
        self.optimizer = optimizer
        self.tc = tc
        self.mesh = mesh
        self.dp_axes = tuple(dp_axes)
        self.compressor = make_compressor(tc)
        self._shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        self.plan = build_plan(
            self._shapes,
            bucket_bytes=tc.bucket_bytes,
            max_buckets=tc.max_buckets,
            interval=tc.interval,
        )
        self._steps: dict[int, Callable] = {}
        # phases whose executable has had its donation read (the
        # ``train_state_donated_share`` gauge, set on each one's first call)
        self._donation_read: set[int] = set()
        self.history: list[dict] = []
        self.runtime = None          # AdaptiveRuntime of the last run(), if any
        self.resilience = None       # ResilienceRuntime of the last run(), if any
        self.transitions: list = []  # TransitionReports from re-plans
        # telemetry bundle (repro.obs): registry + event log + tracer.
        # Defaults to the disabled singleton; run(telemetry=...) swaps in a
        # live bundle (the adaptive runtime and flush_sync write through it)
        from repro.obs import NULL_TELEMETRY

        self.telemetry = NULL_TELEMETRY
        # sharded sync (DESIGN.md §13): True while the last step's deferred
        # param all-gather has not been issued yet (the optimizer left
        # non-owner shards stale).  Each sharded step's head gather settles
        # it implicitly; flush_sync() settles it at run boundaries so the
        # state handed back always carries fresh full params.
        self._pending_sync: bool = False
        self._flush_fns: dict[int, Callable] = {}

    @property
    def num_phases(self) -> int:
        base = self.compressor.num_phases(self.tc.interval)
        if self.tc.pod_interval > 1 and "pod" in self.dp_axes:
            import math as _m
            return _m.lcm(base, self.tc.pod_interval)
        return base

    @property
    def dp_world(self) -> int:
        """World size of the compressor's collectives (excludes the 'pod'
        axis in hierarchical mode, where pods sync via pod_reconcile)."""
        axes = self.dp_axes
        if self.hierarchical:
            axes = tuple(a for a in axes if a != "pod")
        w = 1
        if self.mesh is not None:
            for a in axes:
                w *= self.mesh.shape[a]
        return w

    def schedules(self) -> list[CommSchedule]:
        """Static comm plan of every phase — available before (and without)
        compiling a single executable.

        Hierarchical mode: one schedule per phase of the FULL lcm cycle,
        each carrying the intra-pod gradient calls (link="ici") merged
        with that step's cross-pod reconciliation calls (link="dcn", plus
        the intra AG rebuild under allreduce sync) — the per-link byte
        accounting the adaptive controller and the HLO cross-check read."""
        n = max(self.compressor.num_phases(self.tc.interval), 1)
        base = [
            self.compressor.plan_phase(self.plan, p, world=self.dp_world)
            for p in range(n)
        ]
        if not self.hierarchical:
            return base
        n_pods = self.mesh.shape["pod"] if self.mesh is not None else 1
        out = []
        for p in range(self.num_phases):
            g = base[p % n]
            pod = plan_pod_schedule(
                self.plan,
                pod_phase=p % self.tc.pod_interval,
                pod_interval=self.tc.pod_interval,
                sync=self.tc.sync,
                intra_world=self.dp_world,
                n_pods=n_pods,
            )
            ranks = g.ready_ranks
            if ranks:
                # pod calls issue after every gradient collective
                ranks = ranks + tuple(
                    range(len(ranks), len(ranks) + len(pod.calls))
                )
            out.append(dataclasses.replace(
                g, phase=p, num_phases=self.num_phases,
                calls=g.calls + pod.calls, ready_ranks=ranks,
            ))
        return out

    def schedule_report(self) -> dict:
        scheds = self.schedules()
        mean = mean_bytes_per_step(scheds)
        out = {
            "compressor": self.tc.compressor,
            "num_phases": len(scheds),
            "bytes_per_worker_per_phase": [s.bytes_per_worker for s in scheds],
            "mean_bytes_per_step": mean,
            "dense_bytes": scheds[0].dense_bytes if scheds else 0,
            "volume_ratio": (
                scheds[0].dense_bytes / max(mean, 1) if scheds else 1.0
            ),
        }
        if self.sharded:
            n = max(len(scheds), 1)
            out["sync"] = self.tc.sync
            out["mean_exposed_wire_bytes_per_step"] = (
                sum(s.exposed_wire_bytes(self.dp_world) for s in scheds) / n
            )
            out["mean_deferred_bytes_per_step"] = (
                sum(s.deferred_bytes_per_worker for s in scheds) / n
            )
        return out

    def _phase_fn(self, phase: int) -> Callable:
        if phase not in self._steps:
            self._steps[phase] = build_train_step(
                self.model, self.optimizer, self.compressor, self.plan,
                phase=phase, mesh=self.mesh, dp_axes=self.dp_axes,
                clip_norm=self.tc.clip_norm, donate=True,
                pod_interval=self.tc.pod_interval,
                overlap=self.tc.overlap,
            )
        return self._steps[phase]

    @property
    def hierarchical(self) -> bool:
        return self.tc.pod_interval > 1 and "pod" in self.dp_axes

    @property
    def sharded(self) -> bool:
        return self.tc.sync == "sharded"

    # ---- sharded sync bookkeeping (DESIGN.md §13) -------------------------
    def _flush_fn(self) -> Callable:
        if 0 not in self._flush_fns:
            from repro.core.overlap import sharded_param_allgather

            # the gather covers every bucket, so any phase's schedule works
            schedule = self.compressor.plan_phase(
                self.plan, 0, world=self.dp_world
            )
            hier = self.hierarchical
            # hierarchical: each pod's shard owners hold that pod's
            # authoritative values, so the settling gather runs over the
            # intra-pod axes only — pods keep their (bounded) drift
            axes = (
                tuple(a for a in self.dp_axes if a != "pod")
                if hier else self.dp_axes
            )
            params_def = jax.tree_util.tree_structure(
                jax.tree.map(lambda _: 0, self._shapes)
            )

            def gather(tree):
                return sharded_param_allgather(
                    self.compressor, schedule, tree, axis_names=axes
                )

            def gather_like_params(tree):
                """Gather every params-shaped subtree (Adam's m/v, SGD's
                mu) — the shard owners hold the exact moments the
                allreduce path would have, so the gathered state is fully
                portable (checkpoint-restorable under any sync mode or
                world size)."""
                if (
                    jax.tree_util.tree_structure(
                        jax.tree.map(lambda _: 0, tree)
                    )
                    == params_def
                ):
                    return gather(tree)
                if isinstance(tree, dict):
                    return {
                        k: gather_like_params(v) for k, v in tree.items()
                    }
                return tree

            def flush(params, opt):
                if hier:
                    params, opt = strip_pod_block((params, opt))
                out = gather(params), gather_like_params(opt)
                if hier:
                    out = restore_pod_block(out)
                return out

            spec = P("pod") if hier else P()
            mapped = jax.shard_map(
                flush, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=(spec, spec), axis_names=set(self.dp_axes),
                check_vma=False,
            )
            self._flush_fns[0] = jax.jit(mapped)
        return self._flush_fns[0]

    def flush_sync(self, state):
        """Settle the pending deferred gathers (sharded sync): at run
        boundaries — end of ``run``, checkpoint saves, re-plans, state
        inspection — the last step's updated shards must be gathered so
        params AND optimizer moments are fully fresh on every worker
        (owner shards carry the exact allreduce-equivalent values, so the
        flushed state checkpoints/restores portably).  No-op for
        ``allreduce`` runs, single-process runs, and when nothing is
        pending."""
        if not self.sharded or not self._pending_sync:
            return state
        self._pending_sync = False
        if self.telemetry.enabled:
            self.telemetry.events.emit(
                "flush", step=int(state["step"]), reason="deferred-allgather"
            )
        if self.mesh is None or not self.dp_axes:
            return state      # single worker: shards ARE the full params
        params, opt = self._flush_fn()(state["params"], state["opt"])
        return {**state, "params": params, "opt": opt}

    def init_state(self, key):
        state = make_train_state(self.model, self.optimizer, self.compressor,
                                 self.plan, key)
        if self.hierarchical:
            n_pods = self.mesh.shape["pod"]
            for k in ("params", "opt", "comp"):
                state[k] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (n_pods,) + a.shape),
                    state[k],
                )
        return state

    def replan(self, interval: int, state=None, *, policy: str = "carry",
               step: int = 0, old_interval: int | None = None):
        """Adopt a new COVAP interval at a safe boundary (between steps):
        new compressor + bucket plan + (lazily recompiled) phase
        executables, with the EF residual carried across the switch by
        ``runtime.transitions`` so its norm survives the transition.

        ``old_interval`` is the cadence the residual in ``state`` was
        accumulated under; it defaults to this trainer's current interval
        and must be given explicitly when the state came from elsewhere
        (e.g. a checkpoint saved under a different config).

        Returns ``(state, TransitionReport)`` — ``state`` unchanged (may be
        None) when the caller manages compressor state itself."""
        from repro.runtime.transitions import carry_comp_state

        if old_interval is None:
            old_interval = self.tc.interval
        if state is not None:
            # sharded sync: the pending deferred AG references the OLD
            # plan's schedules — settle it before the plan is replaced
            state = self.flush_sync(state)
        self.tc = dataclasses.replace(self.tc, interval=int(interval))
        self.compressor = make_compressor(self.tc)
        self.plan = build_plan(
            self._shapes,
            bucket_bytes=self.tc.bucket_bytes,
            max_buckets=self.tc.max_buckets,
            interval=self.tc.interval,
        )
        self._steps = {}   # stale executables: new phases compile lazily
        self._donation_read = set()
        self._flush_fns = {}
        report = None
        if state is not None:
            comp, report = carry_comp_state(
                state["comp"],
                new_compressor=self.compressor,
                new_plan=self.plan,
                params_like=state["params"],
                step=step,
                old_interval=old_interval,
                new_interval=self.tc.interval,
                policy=policy,
            )
            state = {**state, "comp": comp}
            self.transitions.append(report)
        return state, report

    def run(self, state, batches, steps: int | None = None, log=print,
            autotune=None, telemetry=None, guards=None, faults=None):
        """Host loop.  ``autotune`` (None | True | AutotuneConfig | a live
        AdaptiveRuntime) arms the adaptive runtime: measured-CCR monitoring
        + hysteresis re-planning + timeline tracing (DESIGN.md §10).
        Passing an ``AdaptiveRuntime`` keeps its monitor/controller state
        across chunked ``run`` calls (checkpoint-every loops) instead of
        restarting the policy each chunk.  With ``autotune=None`` the loop
        is the PR-1 static path, bit-for-bit.

        ``telemetry`` (None | directory path | :class:`repro.obs.Telemetry`)
        arms the unified telemetry subsystem (DESIGN.md §15): a run
        manifest + step records into the JSONL event log, loss/grad-norm/
        step counters into the metrics registry, and — when the adaptive
        runtime is armed too — the runtime's planned/measured/control
        spans land in the bundle's shared tracer.  All recording happens
        at the existing log cadence (metrics are already host-side floats
        there), so the hot loop gains no extra device syncs; with
        ``telemetry=None`` every hook is a no-op on the shared disabled
        singleton.

        ``guards`` (None | True | GuardConfig | dict of overrides) arms
        the resilience runtime (DESIGN.md §16): numeric guardrails on
        each step's metrics plus the skip-step -> EF-flush -> checkpoint-
        rewind recovery ladder.  ``faults`` (None | spec string |
        FaultPlan | FaultInjector) arms deterministic fault injection for
        chaos runs; a live :class:`~repro.resilience.ResilienceRuntime`
        passed as ``guards`` keeps its ladder/injector state across
        chunked ``run`` calls.  With both None the loop is the prior
        path, bit-for-bit.

        The phase executables donate the params, optimizer moments and
        error-feedback residuals, so each step updates them in place and
        the host can queue the next step while one runs: the ``state``
        passed in is consumed.  Use the returned state; a caller that
        needs the input state afterwards passes a copy
        (:func:`copy_train_state`)."""
        from repro.obs import as_telemetry
        from repro.obs.events import plan_digest

        steps = steps if steps is not None else self.tc.steps
        tel = as_telemetry(telemetry)
        if tel.enabled:
            self.telemetry = tel
            tel.manifest_once(
                role="train",
                config=dataclasses.asdict(self.tc),
                plan={
                    "digest": plan_digest(self.plan),
                    "num_buckets": self.plan.num_buckets,
                    "num_phases": self.num_phases,
                    "bucket_bytes_target": self.plan.bucket_bytes_target,
                },
                world=self.dp_world,
                mesh=(
                    {a: int(self.mesh.shape[a]) for a in self.mesh.shape}
                    if self.mesh is not None else None
                ),
            )
        rt = None
        if autotune is not None and autotune is not False:
            from repro.runtime import AdaptiveRuntime, as_autotune_config

            if isinstance(autotune, AdaptiveRuntime):
                rt = self.runtime = autotune
            else:
                rt = self.runtime = AdaptiveRuntime(
                    self, as_autotune_config(autotune)
                )
            if tel.enabled:
                rt.attach_telemetry(tel)
        res = None
        if guards is not None or faults is not None:
            from repro.resilience import ResilienceRuntime

            if isinstance(guards, ResilienceRuntime):
                res = self.resilience = guards
            else:
                res = self.resilience = ResilienceRuntime(
                    self, guards=guards, faults=faults,
                )
            res.attach_telemetry(tel)
            if res.injector is not None and rt is not None:
                # ccr_skew faults ride the probe path: wrap the runtime's
                # probe dispatch so due events inflate the measured comm
                # time (instance attribute shadows the class method)
                rt._probe = res.injector.wrap_probe(rt._probe)
        it = iter(batches)
        steps_c = tel.registry.counter(
            "train_steps_total", "optimizer steps completed"
        )
        loss_g = tel.registry.gauge("train_loss", "last logged total loss")
        gnorm_g = tel.registry.gauge(
            "train_grad_norm", "last logged global gradient norm"
        )
        t0 = time.perf_counter()
        for i in range(steps):
            # host spans on the profiler's clock: each step, the wait
            # for its batch, its dispatch and the log-cadence host sync;
            # next to free when no trace is being taken
            with jax.profiler.StepTraceAnnotation(
                "train_step", step_num=state["step"]
            ):
                with jax.profiler.TraceAnnotation("train.batch_wait"):
                    batch = next(it)
                if res is not None:
                    # snapshot (a device copy when a check window opens)
                    # -> guard-owned checkpoint -> fault injection
                    state, batch = res.pre_step(state, batch)
                phase = state["step"] % self.num_phases
                fn = self._phase_fn(phase)
                # block for a true wall time only on probe-due steps — an
                # every-step block would serialise async dispatch for the
                # whole run to feed a diagnostic metric
                timed = rt is not None and rt.due_next()
                t_step = time.perf_counter() if timed else 0.0
                handed = (state["params"], state["opt"], state["comp"])
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    params, opt, comp, metrics = fn(
                        *handed, batch, jnp.asarray(state["step"], jnp.int32),
                    )
                if phase not in self._donation_read:
                    self._donation_read.add(phase)
                    tel.registry.gauge(
                        "train_state_donated_share",
                        "share of the handed-in train state's bytes the "
                        "phase executable consumed (donated), first call",
                        phase=str(phase),
                    ).set(_donated_share(handed))
                state = {"params": params, "opt": opt, "comp": comp,
                         "step": state["step"] + 1}
                steps_c.inc()
                if self.sharded:
                    self._pending_sync = True
                if res is not None:
                    # guard check + recovery BEFORE the adaptive runtime sees
                    # the state: a poisoned step must not feed the CCR probe
                    # or cross a re-plan boundary
                    state = res.post_step(state, metrics)
                if rt is not None:
                    wall = None
                    if timed:
                        jax.block_until_ready(params)
                        wall = time.perf_counter() - t_step
                    state = rt.after_step(state, batch, wall_s=wall, log=log)
                if (i + 1) % self.tc.log_every == 0 or i == 0:
                    with jax.profiler.TraceAnnotation("train.host_sync"):
                        m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = state["step"]
                    m["wall_s"] = time.perf_counter() - t0
                    self.history.append(m)
                    if tel.enabled:
                        loss_g.set(m["total_loss"])
                        gnorm_g.set(m["grad_norm"])
                        tel.events.emit(
                            "step",
                            step=int(state["step"]),
                            loss=m["total_loss"],
                            grad_norm=m["grad_norm"],
                            wall_s=m["wall_s"],
                            phase=int(phase),
                            metrics={
                                k: v for k, v in m.items()
                                if k not in ("step", "wall_s")
                            },
                        )
                    if log:
                        # only total_loss/grad_norm are guaranteed — model
                        # metrics dicts need not include a 'loss' key
                        shown = m.get("loss", m["total_loss"])
                        log(
                            f"step {state['step']:>5d}  loss {shown:.4f}  "
                            f"gnorm {m['grad_norm']:.3f}  t {m['wall_s']:.1f}s"
                        )
        if res is not None:
            # drain the lag-one deferred guard check (may recover: the
            # returned state can sit behind the loop's nominal target)
            state = res.finalize(state)
        if rt is not None:
            rt.finish()
        # sharded sync: hand back fully-fresh params (the final step's
        # deferred AG has no next step to ride — settle it here)
        return self.flush_sync(state)
