"""Reusable gradient-sync stages + the ``SyncPipeline`` combinator.

Every GC scheme in this repo decomposes into at most three orthogonal
stages (DESIGN.md SS4):

* an optional :class:`ErrorFeedback` stage (compensate before, keep the
  un-sent part as the residual after);
* an optional :class:`CoarseFilter` (the paper's static bucket selection —
  the only stage that makes a schedule phase-dependent);
* exactly one *wire stage* that defines how a selected bucket (or leaf)
  crosses the interconnect: :class:`WireCast` (dense, optionally
  dtype-cast, segment-wise all-reduce), :class:`TopK`, :class:`RandomK`,
  :class:`SignCompress`, :class:`FP8Block`, :class:`OkTopKRoute`
  (bucket granularity) or :class:`LowRank` (leaf granularity, PowerSGD).

``SyncPipeline`` composes them and implements the plan/execute split:
``plan_phase`` emits a static :class:`CommSchedule` (no tracing), and
``execute`` is a pure function of ``(schedule, grads, state)`` that runs
inside ``shard_map``.  COVAP is literally::

    SyncPipeline(filter=CoarseFilter(I), ef=ErrorFeedback(EFSchedule(...)),
                 wire=WireCast())

and beyond-paper hybrids (filter + fp8 wire + EF, GraVAC-style) are
one-liners: ``SyncPipeline.of(CoarseFilter(8), ErrorFeedback(), FP8Block())``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import arena as ar
from . import bucketing as bk
from .bucketing import Bucket, BucketPlan, build_ready_order
from .error_feedback import EFSchedule, compensate, init_residual
from .filter import selected_buckets
from .schedule import CollectiveCall, CommSchedule
from .comm import (
    Compressor,
    SyncStats,
    all_gather,
    dense_bytes,
    flat_axis_index,
    pmean,
    reduce_scatter,
)


def _bucket_dtype(plan: BucketPlan, bucket: Bucket) -> np.dtype:
    """Dtype of the flattened bucket vector (mixed buckets promote) —
    canonical definition lives in :func:`repro.core.arena.bucket_dtype`."""
    return ar.bucket_dtype(plan, bucket)


# ---------------------------------------------------------------------------
# filter + error-feedback stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CoarseFilter:
    """The paper's coarse-grained filter (SS III.A): bucket ``b`` is
    communicated in phase ``p`` iff ``(b + p) % interval == 0``."""

    interval: int = 4

    def num_phases(self) -> int:
        return max(int(self.interval), 1)

    def select(self, plan: BucketPlan, phase: int) -> tuple[int, ...]:
        return selected_buckets(plan.num_buckets, phase, self.interval)


@dataclasses.dataclass(frozen=True)
class ErrorFeedback:
    """Compensation + residual stage (SS III.D).  ``schedule=None`` is the
    classic EF of the baselines (coefficient 1); COVAP passes its ascending
    :class:`EFSchedule`."""

    schedule: EFSchedule | None = None

    def compensated(self, grads: Any, residual: Any, step) -> Any:
        if self.schedule is None:
            return jax.tree.map(
                lambda g, r: g + r.astype(g.dtype), grads, residual
            )
        return compensate(grads, residual, self.schedule.coefficient(step))


# ---------------------------------------------------------------------------
# wire stages (bucket granularity)
# ---------------------------------------------------------------------------

class WireStage:
    """How one selected bucket crosses the network.

    ``plan_bucket`` is the static half (exact per-worker bytes, collective
    op, wire dtype); ``execute_bucket`` / ``execute_segment`` the traced
    half.  ``segmented=True`` stages work on sharding-preserving segment
    slices (no gather/scatter copies); the rest see the flat bucket vector.
    """

    op: str = "all_reduce"
    segmented: bool = False

    def plan_bucket(
        self, plan: BucketPlan, bucket: Bucket, world: int = 1
    ) -> CollectiveCall:
        raise NotImplementedError

    def execute_bucket(self, flat, key, axis_names):
        """-> (synced_flat, local_sent_flat)"""
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__}()"


class WireCast(WireStage):
    """Dense segment-wise all-reduce, optionally dtype-cast on the wire.

    ``WireCast(None)`` is the DDP baseline (one psum per bucket segment);
    ``WireCast('bfloat16')`` halves the wire volume, with the quantisation
    error landing in the EF residual when an :class:`ErrorFeedback` stage is
    present (beyond-paper COVAP x2 composition).
    """

    segmented = True

    def __init__(self, wire_dtype: str | None = None):
        self.wire_dtype = jnp.dtype(wire_dtype) if wire_dtype else None

    def plan_bucket(self, plan, bucket, world=1):
        if self.wire_dtype is not None:
            payload = bucket.numel * self.wire_dtype.itemsize
            name = self.wire_dtype.name
        else:
            payload = bucket.nbytes
            name = _bucket_dtype(plan, bucket).name
        return CollectiveCall(
            f"bucket:{bucket.index}", "all_reduce", name, payload
        )

    def execute_segment(self, x, axis_names):
        """-> (synced_segment, residual_segment)."""
        if self.wire_dtype is not None and x.dtype != self.wire_dtype:
            from ..kernels.ref import cast_error

            xw, err = cast_error(x, self.wire_dtype)
            return pmean(xw, axis_names).astype(x.dtype), err
        return pmean(x, axis_names), jnp.zeros_like(x)

    def __repr__(self):
        return f"WireCast({self.wire_dtype})"


class TopK(WireStage):
    """Aji & Heafield top-|g| selection; worker index sets differ, so the
    exchange is an all-gather of (values, int32 indices).  ``clip_norm``
    adds DGC's local gradient clipping before selection."""

    op = "all_gather"

    def __init__(self, ratio: float = 0.01, clip_norm: float = 0.0):
        self.ratio = float(ratio)
        self.clip_norm = float(clip_norm)

    def _k(self, n: int) -> int:
        return max(1, int(math.ceil(n * self.ratio)))

    def plan_bucket(self, plan, bucket, world=1):
        dt = _bucket_dtype(plan, bucket)
        m = self._k(bucket.numel)
        return CollectiveCall(
            f"bucket:{bucket.index}", "all_gather", dt.name,
            m * dt.itemsize, m * 4,
        )

    def execute_bucket(self, flat, key, axis_names):
        if self.clip_norm > 0:
            norm = jnp.linalg.norm(flat) + 1e-12
            flat = flat * jnp.minimum(1.0, self.clip_norm / norm)
        n = flat.shape[0]
        m = self._k(n)
        _, idx = lax.top_k(jnp.abs(flat), m)
        vals = flat[idx]
        vals_all = all_gather(vals, axis_names)  # (W, m)
        idx_all = all_gather(idx, axis_names)
        W = vals_all.shape[0]
        out = jnp.zeros(n, flat.dtype)
        out = out.at[idx_all.reshape(-1)].add(vals_all.reshape(-1)) / W
        local_sent = jnp.zeros(n, flat.dtype).at[idx].set(vals)
        return out, local_sent


class RandomK(WireStage):
    """Stich et al. sparsified SGD: the index set comes from a PRNG key
    shared by construction (seed, step, bucket), so the exchange is a dense
    psum over the selected values only — no index traffic."""

    op = "all_reduce"

    def __init__(self, ratio: float = 0.01):
        self.ratio = float(ratio)

    def plan_bucket(self, plan, bucket, world=1):
        dt = _bucket_dtype(plan, bucket)
        m = max(1, int(math.ceil(bucket.numel * self.ratio)))
        return CollectiveCall(
            f"bucket:{bucket.index}", "all_reduce", dt.name, m * dt.itemsize
        )

    def execute_bucket(self, flat, key, axis_names):
        n = flat.shape[0]
        m = max(1, int(math.ceil(n * self.ratio)))
        idx = jax.random.randint(key, (m,), 0, n)
        vals = flat[idx]
        synced = pmean(vals, axis_names)
        out = jnp.zeros(n, flat.dtype).at[idx].set(synced)
        local_sent = jnp.zeros(n, flat.dtype).at[idx].set(vals)
        return out, local_sent


class SignCompress(WireStage):
    """EFsignSGD wire format: int8 signs (1 byte/elem) + one fp32 scale
    = mean(|t|); AllGather-based (scales worse with W — Fig. 11)."""

    op = "all_gather"

    def plan_bucket(self, plan, bucket, world=1):
        return CollectiveCall(
            f"bucket:{bucket.index}", "all_gather", "int8",
            bucket.numel * 1, 4,
        )

    def execute_bucket(self, flat, key, axis_names):
        scale = jnp.mean(jnp.abs(flat))
        signs = jnp.where(flat >= 0, 1, -1).astype(jnp.int8)
        signs_all = all_gather(signs, axis_names)          # (W, n) int8
        scales_all = all_gather(scale[None], axis_names)   # (W, 1)
        decoded = (
            signs_all.astype(flat.dtype) * scales_all.astype(flat.dtype)
        ).mean(axis=0)
        local_sent = scale * signs.astype(flat.dtype)
        return decoded, local_sent


class FP8Block(WireStage):
    """Block-scaled FP8 wire (4x vs fp32): fp8 payload + fp32 per-block
    amax scales, exchanged by all-gather (payloads differ per worker)."""

    op = "all_gather"

    def __init__(self, block: int = 8192):
        self.block = int(block)

    def plan_bucket(self, plan, bucket, world=1):
        nb = max(1, -(-bucket.numel // self.block))
        return CollectiveCall(
            f"bucket:{bucket.index}", "all_gather", "float8_e4m3fn",
            bucket.numel * 1, nb * 4,
        )

    def execute_bucket(self, flat, key, axis_names):
        from ..kernels import ref as kref

        q, scales = kref.quantize_fp8_ref(flat, block=self.block)
        q_all = all_gather(q, axis_names)            # (W, n) fp8
        s_all = all_gather(scales, axis_names)       # (W, nb)
        W = q_all.shape[0]
        dec = jnp.stack(
            [
                kref.dequantize_fp8_ref(q_all[w], s_all[w], block=self.block)
                for w in range(W)
            ]
        ).mean(axis=0).astype(flat.dtype)
        local_sent = kref.dequantize_fp8_ref(
            q, scales, block=self.block
        ).astype(flat.dtype)
        return dec, local_sent


def _all_to_all(x, axis_names):
    """all-to-all over (possibly multiple) named axes; x: (W, ...)."""
    if len(axis_names) == 1:
        return lax.all_to_all(x, axis_names[0], split_axis=0, concat_axis=0)
    return lax.all_to_all(x, tuple(axis_names), split_axis=0, concat_axis=0)


class OkTopKRoute(WireStage):
    """Ok-topk's region-routed sparse exchange (all-to-all with fixed
    capacity + regional top-(k/W) + all-gather of survivors) — the
    data-dependent multi-stage pattern the paper identifies as hostile to
    overlapping (SS I, Fig. 1e)."""

    op = "all_to_all"

    def __init__(self, ratio: float = 0.01):
        self.ratio = float(ratio)

    @staticmethod
    def _geometry(n: int, ratio: float, W: int):
        m = max(W, int(math.ceil(n * ratio)))
        m = int(math.ceil(m / W) * W)
        region_size = int(math.ceil(n / W))
        cap = min(2 * m // W + 1, region_size)
        return m, region_size, cap

    def plan_bucket(self, plan, bucket, world=1):
        dt = _bucket_dtype(plan, bucket)
        W = max(int(world), 1)
        m, _, cap = self._geometry(bucket.numel, self.ratio, W)
        k_r = m // W
        # two physically different exchanges, priced separately so the
        # wire model amplifies each correctly: the routed all-to-all
        # ((vals, int32 idx, mask-at-wire-dtype) x W capacity windows) and
        # the survivor all-gather ((vals, int32 global idx) x k_r)
        return (
            CollectiveCall(
                f"bucket:{bucket.index}", "all_to_all", dt.name,
                W * cap * dt.itemsize, W * cap * (4 + dt.itemsize),
            ),
            CollectiveCall(
                f"bucket:{bucket.index}:survivors", "all_gather", dt.name,
                k_r * dt.itemsize, k_r * 4,
            ),
        )

    def execute_bucket(self, flat, key, axis_names):
        n = flat.shape[0]
        if not axis_names:
            # single worker: reduces to local top-k
            m = max(1, int(math.ceil(n * self.ratio)))
            _, idx = lax.top_k(jnp.abs(flat), m)
            vals = flat[idx]
            out = jnp.zeros(n, flat.dtype).at[idx].set(vals)
            return out, out

        W = lax.axis_size(axis_names[0])
        for ax in axis_names[1:]:
            W *= lax.axis_size(ax)
        m, region_size, cap = self._geometry(n, self.ratio, W)
        n_pad = region_size * W

        _, idx = lax.top_k(jnp.abs(flat), m)
        vals = flat[idx]
        region = idx // region_size  # (m,) destination worker

        # position of each entry within its destination's capacity window
        onehot = (region[:, None] == jnp.arange(W)[None, :]).astype(jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(m), region]

        send_vals = jnp.zeros((W, cap), flat.dtype).at[region, pos].set(
            vals, mode="drop"
        )
        send_idx = jnp.zeros((W, cap), jnp.int32).at[region, pos].set(
            (idx - region * region_size).astype(jnp.int32), mode="drop"
        )
        send_mask = jnp.zeros((W, cap), flat.dtype).at[region, pos].set(
            1.0, mode="drop"
        )

        recv_vals = _all_to_all(send_vals, axis_names)
        recv_idx = _all_to_all(send_idx, axis_names)
        recv_mask = _all_to_all(send_mask, axis_names)

        dense = jnp.zeros(region_size, flat.dtype).at[
            recv_idx.reshape(-1)
        ].add((recv_vals * recv_mask).reshape(-1))
        k_r = m // W
        _, ridx = lax.top_k(jnp.abs(dense), k_r)
        rvals = dense[ridx]
        offset = flat_axis_index(tuple(axis_names)) * region_size
        gidx = ridx + offset

        vals_all = all_gather(rvals, axis_names).reshape(-1)
        gidx_all = all_gather(gidx, axis_names).reshape(-1)
        out = jnp.zeros(n_pad, flat.dtype).at[gidx_all].set(vals_all) / W
        out = out[:n]

        kept = pos < cap
        local_sent = jnp.zeros(n, flat.dtype).at[idx].set(
            jnp.where(kept, vals, 0.0)
        )
        return out, local_sent


# ---------------------------------------------------------------------------
# leaf-granularity wire stage (PowerSGD)
# ---------------------------------------------------------------------------

def _as_batched_matrix(x: jax.Array) -> jax.Array:
    if x.ndim == 2:
        return x[None]
    return x.reshape((-1,) + x.shape[-2:])


class LowRank:
    """PowerSGD's rank-r factorised all-reduce, per >=2-D leaf (batched over
    leading stack axes).  Communication per matrix: (a + b) * r words via
    AllReduce — scales well but pays two matmuls + QR per step."""

    granularity = "leaf"
    op = "all_reduce"

    def __init__(self, rank: int = 2, seed: int = 0):
        self.rank = int(rank)
        self.seed = int(seed)

    def init_state(self, params_like: Any, plan: BucketPlan, *, use_ef: bool):
        key = jax.random.PRNGKey(self.seed)
        qs, resid = [], []
        for i, leaf in enumerate(jax.tree_util.tree_leaves(params_like)):
            if leaf.ndim >= 2:
                m = _as_batched_matrix(jnp.zeros(leaf.shape, leaf.dtype))
                b = m.shape[-1]
                k = jax.random.fold_in(key, i)
                qs.append(
                    jax.random.normal(k, (m.shape[0], b, self.rank), leaf.dtype)
                )
            else:
                qs.append(None)
            resid.append(
                jnp.zeros(leaf.shape, leaf.dtype) if use_ef else None
            )
        return {"q": qs, "residual": resid}

    def plan_leaf(
        self, leaf_idx: int, shape: tuple[int, ...], dtype
    ) -> CollectiveCall:
        dt = np.dtype(dtype)
        if len(shape) >= 2:
            lead = shape[:-2]
            B = int(np.prod(lead, dtype=np.int64)) if lead else 1
            a, b = shape[-2], shape[-1]
            payload = B * (a + b) * self.rank * dt.itemsize
        else:
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            payload = n * dt.itemsize
        return CollectiveCall(f"leaf:{leaf_idx}", "all_reduce", dt.name, payload)

    def execute_leaf(self, t, q, axis_names):
        """-> (approx, new_q); dense pmean for <2-D leaves (q is None)."""
        if q is None:
            return pmean(t, axis_names), None
        m = _as_batched_matrix(t)
        p = pmean(jnp.einsum("bij,bjk->bik", m, q), axis_names)
        p, _ = jnp.linalg.qr(p)  # orthonormalize columns
        qn = pmean(jnp.einsum("bij,bik->bjk", m, p), axis_names)
        approx = jnp.einsum("bik,bjk->bij", p, qn).reshape(t.shape)
        return approx, qn

    def __repr__(self):
        return f"LowRank(rank={self.rank})"


# ---------------------------------------------------------------------------
# the combinator
# ---------------------------------------------------------------------------

def _state_present(state: Any) -> bool:
    return state is not None and state != ()


def _split_like(slices: Sequence[jax.Array], flat: jax.Array) -> list[jax.Array]:
    """Split a flat bucket vector back into pieces shaped like ``slices``."""
    out, off = [], 0
    for x in slices:
        n = int(x.size)
        out.append(lax.dynamic_slice_in_dim(flat, off, n).reshape(x.shape))
        off += n
    return out


class SyncPipeline(Compressor):
    """filter ∘ error-feedback ∘ wire, with the plan/execute split.

    ``plan_phase(plan, phase)`` -> :class:`CommSchedule` (static, no
    tracing); ``execute(schedule, grads, state)`` -> (synced, state', stats)
    (pure, shard_map-safe).  ``sync`` remains as the legacy one-call wrapper.
    """

    name = "pipeline"

    def __init__(
        self,
        *,
        wire,
        filter: CoarseFilter | None = None,
        ef: ErrorFeedback | None = None,
        seed: int = 0,
        **opts,
    ):
        super().__init__(**opts)
        self.wire = wire
        self.filter = filter
        self.ef = ef
        self.seed = int(seed)
        if self.granularity == "leaf" and filter is not None:
            raise ValueError("CoarseFilter requires bucket granularity")
        sync = self.options.get("sync", "allreduce") or "allreduce"
        if sync not in ("allreduce", "sharded"):
            raise ValueError(
                f"sync must be 'allreduce' or 'sharded', got {sync!r}"
            )
        if sync == "sharded" and not (
            self.granularity == "bucket"
            and getattr(self.wire, "segmented", False)
        ):
            raise ValueError(
                "sync='sharded' requires a segmented bucket pipeline "
                f"(covap / none / fp16); {self.wire!r} must use "
                "sync='allreduce'"
            )

    # ---- composition sugar ------------------------------------------------
    @classmethod
    def of(cls, *stages, seed: int = 0, **opts) -> "SyncPipeline":
        """Build a pipeline from an unordered stage list, e.g.
        ``SyncPipeline.of(CoarseFilter(8), ErrorFeedback(), FP8Block())``."""
        filt, ef, wire = None, None, None
        for s in stages:
            if isinstance(s, CoarseFilter):
                filt = s
            elif isinstance(s, ErrorFeedback):
                ef = s
            elif isinstance(s, (WireStage, LowRank)):
                if wire is not None:
                    raise ValueError("exactly one wire stage per pipeline")
                wire = s
            else:
                raise TypeError(f"not a pipeline stage: {s!r}")
        if wire is None:
            wire = WireCast(None)
        return cls(wire=wire, filter=filt, ef=ef, seed=seed, **opts)

    @property
    def granularity(self) -> str:
        return getattr(self.wire, "granularity", "bucket")

    @property
    def sync_mode(self) -> str:
        """Collective decomposition: ``"allreduce"`` (one all-reduce per
        selected bucket — the classic path) or ``"sharded"`` (reduce-scatter
        the compressed gradient, optimizer on the local shard, deferred
        param all-gather at the next step's head — DESIGN.md §13)."""
        return self.options.get("sync", "allreduce") or "allreduce"

    @property
    def stages(self) -> tuple:
        out = []
        if self.filter is not None:
            out.append(self.filter)
        if self.ef is not None:
            out.append(self.ef)
        out.append(self.wire)
        return tuple(out)

    def __repr__(self):
        inner = " ∘ ".join(repr(s) for s in self.stages)
        return f"{type(self).__name__}[{inner}]"

    # ---- lifecycle --------------------------------------------------------
    def num_phases(self, interval: int | None = None) -> int:
        return self.filter.num_phases() if self.filter is not None else 1

    def init_state(self, params_like: Any, plan: BucketPlan) -> Any:
        if self.granularity == "leaf":
            return self.wire.init_state(
                params_like, plan, use_ef=self.ef is not None
            )
        if self.ef is None:
            return ()
        return init_residual(params_like)

    # ---- plan -------------------------------------------------------------
    def _plan_bucket_sharded(
        self, plan: BucketPlan, bucket: Bucket, world: int
    ) -> CollectiveCall:
        """The exposed half of one bucket's sharded sync (DESIGN.md §13): a
        reduce-scatter of the W-aligned wire slot.  ``payload_bytes`` is
        the full padded input buffer at the wire dtype — the per-worker
        *injected* bytes the HLO parser normalises a reduce-scatter result
        to (``launch.hlo_analysis.collective_bytes_per_worker``)."""
        W = max(int(world), 1)
        padded = ar.aligned_numel(bucket.numel, W)
        wd = _bucket_dtype(plan, bucket)
        if isinstance(self.wire, WireCast) and self.wire.wire_dtype is not None:
            wd = np.dtype(self.wire.wire_dtype)
        return CollectiveCall(
            f"bucket:{bucket.index}", "reduce_scatter", np.dtype(wd).name,
            padded * np.dtype(wd).itemsize,
        )

    def _plan_deferred_allgather(
        self, plan: BucketPlan, world: int
    ) -> tuple[CollectiveCall, ...]:
        """The deferred half of sharded sync: the param all-gathers the
        trainer issues at the next step's head.  One call per plan bucket —
        EVERY bucket, not just this phase's selected ones: once a bucket
        has been selected its optimizer moments are nonzero, so its params
        keep moving every step (Adam decay) and only the shard owner holds
        authoritative values.  Payload is the LOCAL shard each worker
        contributes, at the promoted PARAM dtype (updated parameters go on
        the wire uncompressed — compression applies to gradients only)."""
        W = max(int(world), 1)
        calls = []
        for bucket in plan.buckets:
            padded = ar.aligned_numel(bucket.numel, W)
            pd = _bucket_dtype(plan, bucket)
            calls.append(
                CollectiveCall(
                    f"param-bucket:{bucket.index}", "all_gather",
                    np.dtype(pd).name,
                    (padded // W) * np.dtype(pd).itemsize, deferred=True,
                )
            )
        return tuple(calls)

    def plan_phase(
        self, plan: BucketPlan, phase: int, *, world: int = 1
    ) -> CommSchedule:
        n = self.num_phases()
        ph = int(phase) % max(n, 1)
        ready_ranks: tuple[int, ...] = ()
        sharded = self.sync_mode == "sharded"
        if self.granularity == "leaf":
            selected = tuple(range(len(plan.leaf_shapes)))
            calls = tuple(
                self.wire.plan_leaf(i, plan.leaf_shapes[i], plan.leaf_dtypes[i])
                for i in selected
            )
        else:
            sel = (
                self.filter.select(plan, ph)
                if self.filter is not None
                else tuple(range(plan.num_buckets))
            )
            # a wire stage may plan several collectives per bucket
            # (e.g. OkTopKRoute's route + survivor exchange); `selected`
            # repeats the bucket index so it stays aligned with `calls`
            ready = build_ready_order(plan)
            selected, calls, ranks = [], [], []
            for b in sel:
                planned = (
                    self._plan_bucket_sharded(plan, plan.buckets[b], world)
                    if sharded
                    else self.wire.plan_bucket(plan, plan.buckets[b], world)
                )
                for call in planned if isinstance(planned, tuple) else (planned,):
                    selected.append(b)
                    calls.append(call)
                    ranks.append(ready.rank_of(b))
            selected, calls = tuple(selected), tuple(calls)
            ready_ranks = tuple(ranks)
        return CommSchedule(
            compressor=self.name,
            phase=ph,
            num_phases=max(n, 1),
            granularity=self.granularity,
            selected=selected,
            calls=calls,
            dense_bytes=dense_bytes(plan),
            world=world,
            plan=plan,
            ready_ranks=ready_ranks,
            sync="sharded" if sharded else "allreduce",
            deferred_calls=(
                self._plan_deferred_allgather(plan, world) if sharded else ()
            ),
        )

    # ---- execute ----------------------------------------------------------
    def execute(
        self,
        schedule: CommSchedule,
        grads: Any,
        state: Any,
        *,
        step=0,
        axis_names: Sequence[str] = (),
    ):
        stats = SyncStats(schedule.bytes_per_worker, schedule.dense_bytes)
        if self.granularity == "leaf":
            out, new_state = self._execute_leaf(grads, state, axis_names)
        elif getattr(self.wire, "segmented", False):
            out, new_state = self._execute_segmented(
                schedule, grads, state, step, axis_names
            )
        else:
            out, new_state = self._execute_flat(
                schedule, grads, state, step, axis_names
            )
        return out, new_state, stats

    # ---- granular per-bucket API (overlap engine entry points) ------------
    def ef_coefficient(self, step):
        """The EF compensation coefficient for ``step`` — ``None`` when the
        pipeline has no EF stage (classic EF without a schedule is exactly
        coefficient 1, which is bitwise-identical to the plain add)."""
        if self.ef is None:
            return None
        if self.ef.schedule is None:
            return jnp.float32(1.0)
        return self.ef.schedule.coefficient(step)

    def _use_ef_kernel(self, g, r, coeff) -> bool:
        """The fused Pallas EF-update (kernels/ef_covap.ef_update) replaces
        the 2-3-op jnp formulation on the dense segmented path: one
        streaming pass computes t = g + c*r and splits it into
        (send, residual').  Applicability: plain WireCast (no wire cast —
        the cast path keeps its quantisation-error residual) and f32
        operands.

        Engagement: on TPU by default; on CPU only with the explicit
        ``use_ef_kernel=True`` compressor option.  The fused kernel emits a
        single-rounding FMA for ``g + c*r`` while the jnp formulation
        rounds the product separately, so interpret mode cannot be
        bitwise-identical to the legacy path — CPU runs keep the reference
        formulation unless a test/benchmark opts in (both the post and the
        fused overlap path route through here, so they always agree with
        each other either way)."""
        if not (
            coeff is not None
            and r is not None
            and isinstance(self.wire, WireCast)
            and self.wire.wire_dtype is None
            and g.dtype == jnp.float32
            and r.dtype == jnp.float32
        ):
            return False
        use = self.options.get("use_ef_kernel")
        if use is None:
            from ..kernels.common import INTERPRET

            use = not INTERPRET
        return bool(use)

    # ---- zero-copy arena path (core/arena.py, DESIGN.md §12) --------------
    def _arena_on(self) -> bool:
        """The ``use_arena`` compressor option: bucket payloads live as
        static-offset views of per-phase flat planes instead of per-step
        ``concatenate`` / ``dynamic_slice`` rebuilds.  Off by default — the
        legacy op order stays pinned; arena-on is bitwise-equal for
        uniform-dtype models (mixed-dtype buckets promote per
        :func:`arena.bucket_dtype`, exactly as ``jnp.concatenate`` would,
        so the flat wires match there too)."""
        return bool(self.options.get("use_arena", False))

    def _use_pack_kernel(self, g, r, coeff) -> bool:
        """Fused Pallas pack kernel (kernels/pack_ef_cast.pack_ef_cast) on
        the arena pack pass: one streaming pass computes ``t = g + c*r``,
        the wire-dtype cast, and the residual split — replacing the
        flatten -> compensate -> cast triple materialisation.

        Applicability: EF present, ``WireCast`` wire (dense or bf16/f16
        cast), f32 operands.  Engagement mirrors ``_use_ef_kernel``: on by
        default on TPU, CPU opt-in via ``use_pack_kernel=True`` (interpret
        mode emits a single-rounding FMA for ``g + c*r``, so the CPU
        default stays on the bitwise-identical jnp reference)."""
        if not (
            coeff is not None
            and r is not None
            and isinstance(self.wire, WireCast)
            and g.dtype == jnp.float32
            and r.dtype == jnp.float32
        ):
            return False
        wd = self.wire.wire_dtype
        if wd is not None and wd not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)
        ):
            return False
        use = self.options.get("use_pack_kernel")
        if use is None:
            from ..kernels.common import INTERPRET

            use = not INTERPRET
        return bool(use)

    def _pack_segment(self, g, r, coeff, *, selected: bool):
        """One segment through the fused pack + EF + cast pass.

        Returns ``(wire_flat, resid)``: the flat wire-dtype values destined
        for the segment's arena slot (zeros for an unselected bucket —
        never written) and the new residual in the segment's shape
        (``None`` when EF is off)."""
        gf = g.reshape(-1)
        wd = self.wire.wire_dtype if isinstance(self.wire, WireCast) else None
        if self._use_pack_kernel(g, r, coeff):
            from ..kernels.pack_ef_cast import pack_ef_cast

            w, rnew = pack_ef_cast(
                gf, r.reshape(-1).astype(g.dtype), coeff,
                selected=selected,
                wire_dtype=wd.name if wd is not None else None,
            )
        else:
            from ..kernels import ref as kref

            w, rnew = kref.pack_ef_cast_ref(
                gf,
                r.reshape(-1).astype(g.dtype) if r is not None else None,
                coeff, selected=selected, wire_dtype=wd,
            )
        if rnew is not None:
            rnew = rnew.reshape(g.shape)
        return w, (rnew if r is not None else None)

    def _execute_bucket_arena(
        self, schedule, b, g_slices, r_slices, *, coeff, axis_names
    ):
        """Arena form of one segmented bucket's sync: pack the segments
        into the bucket's contiguous slot (fused EF + cast, static
        offsets), ONE collective over the slot view, split the result with
        static slices.  vs. the legacy per-segment path: no per-segment
        collectives, no dynamic-slice chains — and bitwise-identical
        outputs for uniform-dtype buckets (elementwise ops and ``pmean``
        commute with layout).  A MIXED-dtype bucket reduces at the
        promoted plane dtype (legacy reduces each segment at its own
        dtype), so there the sum's bits — and the dense wire bytes vs the
        planned ``bucket.nbytes`` — legitimately differ; the pinned
        parity guarantee (TrainConfig.arena) is scoped to uniform-dtype
        models."""
        plan = schedule.plan
        selected = b in schedule.selected
        layout = ar.build_layout(
            plan, (b,),
            wire_dtype=(
                self.wire.wire_dtype
                if isinstance(self.wire, WireCast) else None
            ),
        )
        ef_on = r_slices is not None
        wires, resids = [], []
        for g, r in zip(
            g_slices, r_slices if ef_on else (None,) * len(g_slices)
        ):
            w, rnew = self._pack_segment(g, r, coeff, selected=selected)
            wires.append(w)
            resids.append(rnew)
        if not selected:
            return None, (resids if ef_on else None)
        planes = layout.assemble({b: wires})
        xm = pmean(layout.bucket_view(planes, b), axis_names)
        synced = [
            piece.astype(g.dtype)
            for piece, g in zip(layout.unpack_bucket(b, xm), g_slices)
        ]
        return synced, (resids if ef_on else None)

    # ---- sharded sync (reduce-scatter over the arena, DESIGN.md §13) ------
    def _reduce_scatter_slot(self, view, axis_names):
        """One W-aligned slot view through the sharded collective: a
        reduce-scatter (mean, same elementwise op order as ``pmean``) hands
        this worker its reduced shard; the shard is placed back at its
        owner offset in an otherwise-ZERO slot-sized vector.

        The zeros are the sharded contract: only the locally-owned 1/W of
        each bucket carries meaningful synced values — the optimizer's
        updates elsewhere are dead compute whose results are overwritten by
        the owner's shard when ``overlap.sharded_param_allgather`` runs at
        the next step's head.  Single-worker (no axes): identity.
        """
        if not axis_names:
            return reduce_scatter(view, axis_names)
        W = 1
        for a in axis_names:
            W *= lax.axis_size(a)
        shard = reduce_scatter(view, axis_names)
        start = flat_axis_index(axis_names) * (view.shape[0] // W)
        return lax.dynamic_update_slice(
            jnp.zeros_like(view), shard, (start,)
        )

    def _execute_bucket_sharded(
        self, schedule, b, g_slices, r_slices, *, coeff, axis_names
    ):
        """Sharded form of one segmented bucket's sync: pack the segments
        into the bucket's W-aligned contiguous slot (same fused EF + cast
        pass as the arena path — ``pack_ef_cast_ref`` is op-for-op the
        legacy ``_ef_segment`` math), reduce-scatter the slot view, and
        return segment pieces that hold the reduced values at the
        locally-owned shard and zeros elsewhere.  EF residuals are computed
        locally BEFORE the collective, so they are bitwise the allreduce
        path's residuals regardless of the decomposition."""
        plan = schedule.plan
        selected = b in schedule.selected
        ef_on = r_slices is not None
        wires, resids = [], []
        for g, r in zip(
            g_slices, r_slices if ef_on else (None,) * len(g_slices)
        ):
            w, rnew = self._pack_segment(g, r, coeff, selected=selected)
            wires.append(w)
            resids.append(rnew)
        if not selected:
            return None, (resids if ef_on else None)
        W = 1
        for a in axis_names:
            W *= lax.axis_size(a)
        layout = ar.build_layout(
            plan, (b,),
            wire_dtype=(
                self.wire.wire_dtype
                if isinstance(self.wire, WireCast) else None
            ),
            align=W,
        )
        planes = layout.assemble({b: wires})
        full = self._reduce_scatter_slot(
            layout.bucket_view(planes, b), axis_names
        )
        synced = [
            piece.astype(g.dtype)
            for piece, g in zip(layout.unpack_bucket(b, full), g_slices)
        ]
        return synced, (resids if ef_on else None)

    def _ef_segment(self, g, r, coeff, *, selected: bool, axis_names):
        """One segment slice through EF ∘ filter-decision ∘ wire.

        ``g`` is the raw gradient slice, ``r`` the residual slice (or
        ``None`` when the pipeline runs without EF), ``coeff`` the
        compensation coefficient from :meth:`ef_coefficient`.  Returns
        ``(synced, resid)``: the globally-synced value (``None`` for an
        unselected bucket — the caller's output stays zero there) and the
        new residual slice (``None`` when EF is off).
        """
        if self._use_ef_kernel(g, r, coeff):
            from ..kernels.ef_covap import ef_update

            send, rnew = ef_update(
                g.reshape(-1), r.reshape(-1).astype(g.dtype), coeff,
                selected=selected,
            )
            rnew = rnew.reshape(g.shape)
            if not selected:
                return None, rnew
            return pmean(send.reshape(g.shape), axis_names), rnew
        if r is None:
            t = g
        elif coeff is None:
            t = g + r.astype(g.dtype)
        else:
            t = g + coeff * r.astype(g.dtype)
        if not selected:
            return None, (t if r is not None else None)
        xm, resid = self.wire.execute_segment(t, axis_names)
        return xm, (resid if r is not None else None)

    def execute_bucket(
        self,
        schedule: CommSchedule,
        b: int,
        g_slices: Sequence[jax.Array],
        r_slices: Sequence[jax.Array] | None = None,
        *,
        coeff=None,
        key=None,
        axis_names: Sequence[str] = (),
    ):
        """Execute exactly ONE bucket's synchronisation — the granular unit
        the overlap engine's gradient-ready hooks call from inside the
        backward pass, and which :meth:`execute` loops over.

        ``g_slices`` are segment-aligned slices of bucket ``b``
        (``plan.buckets[b].segments`` order); ``r_slices`` the matching EF
        residual slices or ``None``.  Segmented wires take RAW gradient
        slices (EF compensation — fused kernel when applicable — happens in
        here, so the hook path and the post path share one implementation);
        flat wires take already-compensated slices (their classic EF
        residual ``t - sent`` is a whole-tree property handled by the
        caller).

        Returns ``(synced_slices, resid_slices)`` aligned with the bucket's
        segments; ``synced_slices`` is ``None`` for an unselected segmented
        bucket (nothing crosses the wire — output stays zero), and
        ``resid_slices`` is ``None`` when no EF state is threaded.  For
        flat wires ``resid_slices`` carries the *locally sent* values
        (classic EF subtracts them from ``t``).
        """
        plan = schedule.plan
        bucket = plan.buckets[b]
        if self.granularity == "leaf":
            raise ValueError("leaf-granularity pipelines have no buckets; "
                             "use execute_leaf_one")
        selected = b in schedule.selected
        if getattr(self.wire, "segmented", False):
            if schedule.sync == "sharded":
                return self._execute_bucket_sharded(
                    schedule, b, g_slices, r_slices,
                    coeff=coeff, axis_names=axis_names,
                )
            if self._arena_on():
                return self._execute_bucket_arena(
                    schedule, b, g_slices, r_slices,
                    coeff=coeff, axis_names=axis_names,
                )
            synced, resids = [], []
            for g, r in zip(
                g_slices,
                r_slices if r_slices is not None else (None,) * len(g_slices),
            ):
                xm, rr = self._ef_segment(
                    g, r, coeff, selected=selected, axis_names=axis_names
                )
                synced.append(xm)
                resids.append(rr)
            if not selected:
                return None, (resids if r_slices is not None else None)
            return synced, (resids if r_slices is not None else None)
        # flat wire: gather the (compensated) slices, one wire exchange,
        # split synced/sent back into segment-shaped pieces
        if not selected:
            return None, None
        flat = jnp.concatenate([x.reshape(-1) for x in g_slices])
        synced_flat, sent_flat = self.wire.execute_bucket(
            flat, key, axis_names
        )
        return (
            _split_like(g_slices, synced_flat),
            _split_like(g_slices, sent_flat),
        )

    def execute_leaf_one(self, leaf_idx: int, t, q, axis_names):
        """Granular leaf path (LowRank/PowerSGD): sync one compensated leaf
        -> ``(approx, new_q)``."""
        return self.wire.execute_leaf(t, q, axis_names)

    # ---- whole-tree execute paths, rebuilt on the granular API ------------
    def _execute_segmented_arena(self, schedule, grads, state, step, axis_names):
        """Arena form of :meth:`_execute_segmented`: ONE pack pass writes
        every selected bucket's compensated, wire-cast payload into its
        static slot (fused pack kernel where applicable), each bucket's
        collective runs over a contiguous slice view, and results scatter
        back through static-offset segment writes — no per-bucket
        ``concatenate`` rebuilds, no ``dynamic_slice_in_dim`` chains.
        Unselected buckets never touch the arena: their residual update is
        the same fused pack pass with the wire write elided."""
        plan = schedule.plan
        ef_on = self.ef is not None and _state_present(state)
        coeff = self.ef_coefficient(step) if ef_on else None

        treedef = jax.tree_util.tree_structure(grads)
        leaves = jax.tree_util.tree_leaves(grads)
        r_leaves = jax.tree_util.tree_leaves(state) if ef_on else None

        sel = dict.fromkeys(schedule.selected)  # unique, order kept
        wd = self.wire.wire_dtype if isinstance(self.wire, WireCast) else None
        sharded = schedule.sync == "sharded"
        W = 1
        if sharded:
            for a in axis_names:
                W *= lax.axis_size(a)
        layout = ar.build_layout(plan, sel, wire_dtype=wd, align=W)

        # ---- pack pass: one streaming traversal of the gradient ----------
        wire_pieces: dict[int, list] = {}
        resid_pieces: dict[int, list] = {}
        todo = range(plan.num_buckets) if ef_on else sel
        for b in todo:
            selected = b in sel
            pieces, rps = [], []
            for seg in plan.buckets[b].segments:
                g = bk._slice_segment(leaves[seg.leaf_idx], seg)
                r = (
                    bk._slice_segment(r_leaves[seg.leaf_idx], seg)
                    if ef_on else None
                )
                w, rnew = self._pack_segment(g, r, coeff, selected=selected)
                pieces.append(w)
                rps.append(rnew)
            if selected:
                wire_pieces[b] = pieces
            if ef_on:
                resid_pieces[b] = rps
        planes = layout.assemble(wire_pieces)

        # ---- wire pass: one collective per bucket, over a slice view -----
        # (sharded: reduce-scatter the W-aligned slot instead of an
        # all-reduce; the unpacked pieces carry zeros off the owned shard)
        # named_scope per bucket: metadata-only labels so XLA/Perfetto
        # profiles attribute each slot collective to its bucket
        synced_pieces = {}
        for b in sel:
            with jax.named_scope(
                f"covap_arena_bucket_{b}/phase_{schedule.phase}"
            ):
                slot = layout.bucket_view(planes, b)
                wired = (
                    self._reduce_scatter_slot(slot, axis_names)
                    if sharded
                    else pmean(slot, axis_names)
                )
                synced_pieces[b] = layout.unpack_bucket(b, wired)

        # ---- reassembly: one concat per leaf, no update-slice chains -----
        out_leaves = ar.gather_leaves(
            plan,
            lambda b, si, seg: (
                synced_pieces[b][si] if b in synced_pieces else None
            ),
            leaves,
        )
        out = jax.tree_util.tree_unflatten(treedef, out_leaves)
        if ef_on:
            resid_leaves = ar.gather_leaves(
                plan, lambda b, si, seg: resid_pieces[b][si], leaves
            )
            new_state = jax.tree_util.tree_unflatten(treedef, resid_leaves)
        else:
            new_state = state
        return out, new_state

    def _execute_segmented(self, schedule, grads, state, step, axis_names):
        """Sharding-preserving path (COVAP / dense): per-segment slices,
        zero gather/scatter copies for the common whole-leaf case.  With EF
        on, every bucket (selected or not) flows through
        :meth:`execute_bucket` so the residual update fuses with the
        compensation (ef_covap kernel)."""
        if self._arena_on():
            return self._execute_segmented_arena(
                schedule, grads, state, step, axis_names
            )
        plan = schedule.plan
        ef_on = self.ef is not None and _state_present(state)
        coeff = self.ef_coefficient(step) if ef_on else None

        treedef = jax.tree_util.tree_structure(grads)
        leaves = jax.tree_util.tree_leaves(grads)
        r_leaves = jax.tree_util.tree_leaves(state) if ef_on else None
        out_leaves = [jnp.zeros(l.shape, l.dtype) for l in leaves]
        resid_leaves = (
            [jnp.zeros(l.shape, l.dtype) for l in leaves] if ef_on else None
        )

        todo = (
            range(plan.num_buckets) if ef_on
            else dict.fromkeys(schedule.selected)  # unique, order kept
        )
        for b in todo:
            segs = plan.buckets[b].segments
            g_slices = [
                bk._slice_segment(leaves[s.leaf_idx], s) for s in segs
            ]
            r_slices = (
                [bk._slice_segment(r_leaves[s.leaf_idx], s) for s in segs]
                if ef_on else None
            )
            with jax.named_scope(
                f"covap_bucket_{b}/phase_{schedule.phase}"
            ):
                synced, resids = self.execute_bucket(
                    schedule, b, g_slices, r_slices,
                    coeff=coeff, axis_names=axis_names,
                )
            if synced is not None:
                for seg, xm in zip(segs, synced):
                    out_leaves[seg.leaf_idx] = bk._update_segment(
                        out_leaves[seg.leaf_idx], seg, xm
                    )
            if ef_on and resids is not None:
                for seg, rr in zip(segs, resids):
                    resid_leaves[seg.leaf_idx] = bk._update_segment(
                        resid_leaves[seg.leaf_idx], seg, rr
                    )

        out = jax.tree_util.tree_unflatten(treedef, out_leaves)
        new_state = (
            jax.tree_util.tree_unflatten(treedef, resid_leaves)
            if ef_on
            else state
        )
        return out, new_state

    def _execute_flat_arena(self, schedule, grads, state, step, axis_names):
        """Arena form of :meth:`_execute_flat`: the compensated gradient is
        packed ONCE into per-dtype planes (static offsets, the exact
        element order ``gather_bucket`` produces), each selected bucket's
        wire stage consumes a static slice view, and synced/sent values
        return through static-slice unpacks — bitwise-identical to the
        concat/``_split_like`` path for every flat wire."""
        plan = schedule.plan
        ef_on = self.ef is not None and _state_present(state)
        t = self.ef.compensated(grads, state, step) if ef_on else grads

        treedef = jax.tree_util.tree_structure(t)
        leaves = jax.tree_util.tree_leaves(t)

        sel = dict.fromkeys(schedule.selected)  # unique, order kept
        layout = ar.build_layout(plan, sel)
        planes = ar.pack_leaves(layout, leaves)

        base_key = jax.random.PRNGKey(self.seed)
        base_key = jax.random.fold_in(base_key, jnp.asarray(step, jnp.int32))
        synced_pieces: dict[int, list] = {}
        sent_pieces: dict[int, list] = {}
        for b in sel:
            key = jax.random.fold_in(base_key, plan.buckets[b].index)
            synced_flat, sent_flat = self.wire.execute_bucket(
                layout.bucket_view(planes, b), key, axis_names
            )
            synced_pieces[b] = layout.unpack_bucket(b, synced_flat)
            if ef_on:
                sent_pieces[b] = layout.unpack_bucket(b, sent_flat)
        out_leaves = ar.gather_leaves(
            plan,
            lambda b, si, seg: (
                synced_pieces[b][si] if b in synced_pieces else None
            ),
            leaves,
        )
        out = jax.tree_util.tree_unflatten(treedef, out_leaves)
        if ef_on:
            sent_leaves = ar.gather_leaves(
                plan,
                lambda b, si, seg: (
                    sent_pieces[b][si] if b in sent_pieces else None
                ),
                leaves,
            )
            new_state = jax.tree.map(
                lambda a, b: a - b,
                jax.tree_util.tree_unflatten(treedef, leaves),
                jax.tree_util.tree_unflatten(treedef, sent_leaves),
            )
        else:
            new_state = state
        return out, new_state

    def _execute_flat(self, schedule, grads, state, step, axis_names):
        """Flat-bucket path (sparsifiers / sign / fp8): gather each selected
        bucket to a vector, run the wire stage, scatter back; classic EF
        residual' = t - sent_local."""
        if self._arena_on():
            return self._execute_flat_arena(
                schedule, grads, state, step, axis_names
            )
        plan = schedule.plan
        ef_on = self.ef is not None and _state_present(state)
        t = self.ef.compensated(grads, state, step) if ef_on else grads

        treedef = jax.tree_util.tree_structure(t)
        leaves = jax.tree_util.tree_leaves(t)
        out_leaves = [jnp.zeros(l.shape, l.dtype) for l in leaves]
        sent_leaves = [jnp.zeros(l.shape, l.dtype) for l in leaves]

        base_key = jax.random.PRNGKey(self.seed)
        base_key = jax.random.fold_in(base_key, jnp.asarray(step, jnp.int32))
        for b in dict.fromkeys(schedule.selected):  # unique, order kept
            bucket = plan.buckets[b]
            segs = bucket.segments
            g_slices = [
                bk._slice_segment(leaves[s.leaf_idx], s) for s in segs
            ]
            key = jax.random.fold_in(base_key, bucket.index)
            synced, sent = self.execute_bucket(
                schedule, b, g_slices,
                coeff=None, key=key, axis_names=axis_names,
            )
            for seg, xm, sv in zip(segs, synced, sent):
                out_leaves[seg.leaf_idx] = bk._update_segment(
                    out_leaves[seg.leaf_idx], seg, xm
                )
                if ef_on:
                    sent_leaves[seg.leaf_idx] = bk._update_segment(
                        sent_leaves[seg.leaf_idx], seg, sv
                    )
        out = jax.tree_util.tree_unflatten(treedef, out_leaves)
        if ef_on:
            new_state = jax.tree.map(
                lambda a, b: a - b,
                jax.tree_util.tree_unflatten(treedef, leaves),
                jax.tree_util.tree_unflatten(treedef, sent_leaves),
            )
        else:
            new_state = state
        return out, new_state

    def _execute_leaf(self, grads, state, axis_names):
        """Leaf-granularity path (LowRank/PowerSGD): EF folded into the
        per-leaf loop; residual' = t - global approximation."""
        treedef = jax.tree_util.tree_structure(grads)
        leaves = jax.tree_util.tree_leaves(grads)
        qs, resid = state["q"], state["residual"]
        out_leaves, new_qs, new_resid = [], [], []
        for li, (leaf, q, r) in enumerate(zip(leaves, qs, resid)):
            t = leaf + r.astype(leaf.dtype) if r is not None else leaf
            approx, qn = self.execute_leaf_one(li, t, q, axis_names)
            out_leaves.append(approx)
            new_qs.append(qn)
            if r is not None:
                new_resid.append(
                    jnp.zeros_like(t) if qn is None else t - approx
                )
            else:
                new_resid.append(None)
        out = jax.tree_util.tree_unflatten(treedef, out_leaves)
        return out, {"q": new_qs, "residual": new_resid}


__all__ = [
    "CoarseFilter",
    "ErrorFeedback",
    "WireStage",
    "WireCast",
    "TopK",
    "RandomK",
    "SignCompress",
    "FP8Block",
    "OkTopKRoute",
    "LowRank",
    "SyncPipeline",
]
