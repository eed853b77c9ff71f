"""Threshold-filter kernel for Top-k / DGC sparsification.

The DGC trick: estimate the k-th magnitude from a sample, then a single
streaming pass masks |x| < threshold and counts survivors per block (the
count feeding the variable-length pack).  This replaces the O(N log N)
sort that dominates Top-k's 1560 ms overhead in the paper's Table II.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import ELEMWISE_BLOCK, INTERPRET, lane_call


def _thresh_kernel(x_ref, t_ref, y_ref, c_ref):
    x = x_ref[...]
    keep = jnp.abs(x) >= t_ref[0].astype(x.dtype)
    y_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))
    c_ref[pl.program_id(0)] = jnp.sum(keep.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def threshold_filter(x: jax.Array, threshold: jax.Array, *,
                     block: int = ELEMWISE_BLOCK,
                     interpret: bool | None = None):
    """x: (N,) -> (masked (N,), counts (nblocks,) int32)."""
    interpret = INTERPRET if interpret is None else interpret
    # SMEM holds 32-bit words: the threshold travels as f32 (exact for the
    # f32/bf16 values it is compared with) and is cast back in the kernel
    t = jnp.asarray(threshold, x.dtype).astype(jnp.float32).reshape(1)
    return lane_call(
        _thresh_kernel, (x,), (x.dtype,),
        block=block, interpret=interpret, smem_in=(t,), smem_out=(jnp.int32,),
    )


def sample_threshold(x: jax.Array, ratio: float, sample: int = 4096) -> jax.Array:
    """Estimate the (1-ratio) magnitude quantile from a strided sample."""
    n = x.shape[0]
    stride = max(n // sample, 1)
    s = jnp.abs(x[::stride])
    k = jnp.clip(jnp.int32(s.shape[0] * (1.0 - ratio)), 0, s.shape[0] - 1)
    return jnp.sort(s)[k]
