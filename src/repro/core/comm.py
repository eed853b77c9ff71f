"""Compressor interface + registry, built on the plan/execute split.

Every GC scheme from the paper's Table II is a ``Compressor`` with two
halves (DESIGN.md SS3):

    schedule = comp.plan_phase(plan, phase)          # static, no tracing
    synced, new_state, stats = comp.execute(
        schedule, grads, state, step=step, axis_names=('data',))

``plan_phase`` emits a :class:`~repro.core.schedule.CommSchedule` — the
exact per-phase communication contract (selected buckets, collective op,
wire dtype, bytes per worker) — computable before any XLA graph exists.
``execute`` is a pure function of the schedule that runs inside
``shard_map``.  The legacy one-call ``sync`` remains as a thin wrapper.

``axis_names`` are the *manual* mesh axes of the enclosing ``shard_map`` over
which gradients are reduced (the data-parallel axes).  With
``axis_names=()`` the compressor runs in single-worker mode (unit tests,
compression-overhead benchmarks) — all collectives become identities.

``stats.bytes_per_worker`` always equals ``schedule.bytes_per_worker`` — the
statically-known number of bytes each worker injects into the interconnect
per call; tests cross-check it against the collective bytes parsed from
compiled HLO.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .bucketing import BucketPlan


@dataclasses.dataclass(frozen=True)
class SyncStats:
    bytes_per_worker: int
    dense_bytes: int

    @property
    def volume_ratio(self) -> float:
        return self.dense_bytes / max(self.bytes_per_worker, 1)


def _promote_bf16() -> bool:
    """XLA's CPU AllReducePromotion pass CHECK-fails on bf16 all-reduce
    (hlo_instruction.cc 'Invalid binary instruction opcode copy').  On the
    CPU dry-run backend we promote bf16 collectives to f32; on TPU (the
    target) bf16 goes on the wire directly.  Collective-byte accounting in
    the dry-run notes the 2x inflation for bf16-param archs."""
    mode = os.environ.get("REPRO_PSUM_PROMOTE_BF16", "auto")
    if mode == "never":
        return False
    if mode == "always":
        return True
    return jax.default_backend() == "cpu"


def _reduce(op, x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    if not axis_names:
        return x
    if x.dtype == jnp.bfloat16 and _promote_bf16():
        return op(x.astype(jnp.float32), tuple(axis_names)).astype(jnp.bfloat16)
    return op(x, tuple(axis_names))


def pmean(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    return _reduce(lax.pmean, x, axis_names)


def psum(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    return _reduce(lax.psum, x, axis_names)


def world_size(axis_names: Sequence[str]) -> int | jax.Array:
    if not axis_names:
        return 1
    return lax.psum(1, tuple(axis_names))


def all_gather(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """Gather along a new leading axis; identity (adds axis of 1) if local."""
    if not axis_names:
        return x[None]
    g = x
    for ax in reversed(tuple(axis_names)):
        g = lax.all_gather(g, ax)
        g = g.reshape((-1,) + x.shape)
    return g


def flat_axis_index(axis_names: Sequence[str]):
    """Row-major flat worker index over (possibly multiple) named axes —
    the shard-ownership index of the sharded sync path (worker ``w`` owns
    shard ``w`` of every bucket slot)."""
    idx = lax.axis_index(axis_names[0])
    for ax in axis_names[1:]:
        idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
    return idx


def reduce_scatter(
    x: jax.Array, axis_names: Sequence[str], *, mean: bool = True
) -> jax.Array:
    """Reduce-scatter a flat vector over the DP axes: worker ``w`` receives
    the reduced shard ``x[w*S:(w+1)*S]`` (``S = len(x) // W``; the caller
    pads to a W-divisible length — ``arena.build_layout(align=W)``).

    The mean divides the summed shard by ``W`` *after* the collective —
    elementwise the exact op order of ``pmean`` (sum, then divide), so the
    owned shard is bitwise what the all-reduce path computes.  The same
    ``REPRO_PSUM_PROMOTE_BF16`` guard applies: XLA's CPU backend mishandles
    narrow-dtype reduction computations, so bf16 operands are promoted to
    f32 around the collective on the dry-run backend (TPU keeps bf16 on
    the wire).  With no axes this is the identity (single-worker mode).
    """
    if not axis_names:
        return x
    axes = tuple(axis_names)

    W = 1
    for a in axes:
        W *= lax.axis_size(a)

    def op(v, names):
        s = lax.psum_scatter(v, names, scatter_dimension=0, tiled=True)
        if mean:
            s = s / jnp.asarray(W, v.dtype)
        return s

    if x.dtype == jnp.bfloat16 and _promote_bf16():
        return op(x.astype(jnp.float32), axes).astype(jnp.bfloat16)
    return op(x, axes)


def pod_shard_exchange(x: jax.Array, pod_axes: Sequence[str]) -> jax.Array:
    """Cross-pod mean of an owned shard — the DCN half of the two-level
    hierarchical sync (DESIGN.md §17).  ``x`` is the 1/W_intra shard this
    worker owns after the intra-pod reduce-scatter (or the exact slice of
    an intra-pod-replicated bucket); the exchange averages it with the
    same shard held by the peer workers in every other pod.

    Routed through :func:`pmean` so the ``REPRO_PSUM_PROMOTE_BF16`` guard
    applies exactly as it does to the intra-pod reduce-scatter: bf16
    shards are promoted to f32 around the collective on the CPU dry-run
    backend (XLA's CPU AllReducePromotion pass CHECK-fails on bf16
    all-reduce) and stay bf16 on the TPU wire.  Identity with no axes.
    """
    if not pod_axes:
        return x
    return pmean(x, tuple(pod_axes))


def all_gather_tiled(x: jax.Array, axis_names: Sequence[str]) -> jax.Array:
    """Concatenating all-gather of per-worker shards along axis 0 — the
    inverse of :func:`reduce_scatter`'s scatter (worker order matches
    :func:`flat_axis_index`).  Pure data movement, so no dtype promotion is
    needed (the bf16 CPU guard exists for *reduction* computations only).
    Identity with no axes."""
    if not axis_names:
        return x
    g = x
    for ax in reversed(tuple(axis_names)):
        g = lax.all_gather(g, ax, tiled=True)
    return g


class Compressor:
    """Base class.  Subclasses set ``name`` and implement the plan/execute
    pair (``plan_phase`` + ``execute``); ``sync`` composes the two."""

    name: str = "base"

    def __init__(self, **kw):
        self.options = dict(kw)

    # ---- lifecycle -------------------------------------------------------
    def init_state(self, params_like: Any, plan: BucketPlan) -> Any:
        return ()

    def num_phases(self, interval: int) -> int:
        """How many step-specialised executables the trainer must build."""
        return 1

    # ---- plan: static, computable without tracing -------------------------
    def plan_phase(self, plan: BucketPlan, phase: int, *, world: int = 1):
        """Static communication plan for one phase -> ``CommSchedule``."""
        raise NotImplementedError

    # ---- execute: pure, runs inside shard_map -----------------------------
    def execute(
        self,
        schedule,
        grads: Any,
        state: Any,
        *,
        step=0,
        axis_names: Sequence[str] = (),
    ) -> tuple[Any, Any, SyncStats]:
        raise NotImplementedError

    # ---- legacy one-call wrapper ------------------------------------------
    def sync(
        self,
        grads: Any,
        state: Any,
        *,
        plan: BucketPlan,
        phase: int,
        step,
        axis_names: Sequence[str] = (),
    ) -> tuple[Any, Any, SyncStats]:
        # inside a shard_map trace the axis sizes are static, so the plan
        # can be built for the real world size (world-dependent planners
        # like oktopk report wrong bytes otherwise)
        world = 1
        for a in axis_names:
            try:
                world *= lax.axis_size(a)
            except Exception:  # not inside a mapping over `a`
                world = 1
                break
        schedule = self.plan_phase(plan, phase, world=world)
        return self.execute(
            schedule, grads, state, step=step, axis_names=axis_names
        )

    def __repr__(self):
        opts = ", ".join(f"{k}={v}" for k, v in self.options.items())
        return f"{type(self).__name__}({opts})"


_REGISTRY: dict[str, Callable[..., Compressor]] = {}


def register(name: str):
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def get_compressor(name: str, **kw) -> Compressor:
    if name not in _REGISTRY:
        raise KeyError(f"unknown compressor {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kw)


def available() -> list[str]:
    return sorted(_REGISTRY)


def dense_bytes(plan: BucketPlan) -> int:
    return sum(b.nbytes for b in plan.buckets)
