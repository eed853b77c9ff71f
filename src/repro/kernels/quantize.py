"""Block-scaled FP8 quantize/dequantize kernels.

Beyond-paper compressor substrate: per-block amax scaling into
float8_e4m3fn gives 4x wire compression with far better fidelity than
naive casting.  One fused pass computes the block amax (VPU reduction in
VMEM) and writes the scaled fp8 payload + per-block scale.

Block = ``block`` contiguous elements, one (block/128, 128) lane tile
(``common.lane_call``); the per-block scales live in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import INTERPRET, lane_call

FP8_MAX = 448.0  # float8_e4m3fn max finite


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax / FP8_MAX, 1e-12)
    q_ref[...] = (x / scale).astype(jnp.float8_e4m3fn)
    s_ref[pl.program_id(0)] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[pl.program_id(0)]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_fp8(x: jax.Array, *, block: int = 8192, interpret: bool | None = None):
    """x: (N,) fp32/bf16 -> (q (N,) fp8, scales (nblocks,) fp32)."""
    interpret = INTERPRET if interpret is None else interpret
    return lane_call(
        _quant_kernel, (x,), (jnp.float8_e4m3fn,),
        block=block, interpret=interpret, smem_out=(jnp.float32,),
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequantize_fp8(q: jax.Array, scales: jax.Array, *, block: int = 8192,
                   interpret: bool | None = None) -> jax.Array:
    interpret = INTERPRET if interpret is None else interpret
    (x,) = lane_call(
        _dequant_kernel, (q,), (jnp.float32,),
        block=block, interpret=interpret, smem_in=(scales,),
    )
    return x
