"""EFsignSGD sign-compression kernel: int8 signs + per-block |x| partial
sums in one pass (the scale ``mean(|x|)`` is finished by a tiny jnp
reduction over the per-block partials)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import ELEMWISE_BLOCK, INTERPRET, lane_call


def _sign_kernel(x_ref, s_ref, a_ref):
    x = x_ref[...].astype(jnp.float32)
    s_ref[...] = jnp.where(x >= 0, 1, -1).astype(jnp.int8)
    a_ref[pl.program_id(0)] = jnp.sum(jnp.abs(x))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sign_compress(x: jax.Array, *, block: int = ELEMWISE_BLOCK,
                  interpret: bool | None = None):
    """x: (N,) -> (signs (N,) int8, scale () fp32 = mean|x|)."""
    interpret = INTERPRET if interpret is None else interpret
    n = x.shape[0]
    signs, partials = lane_call(
        _sign_kernel, (x,), (jnp.int8,),
        block=block, interpret=interpret, smem_out=(jnp.float32,),
    )
    scale = jnp.sum(partials) / jnp.float32(max(n, 1))
    return signs, scale


def sign_decompress(signs: jax.Array, scale: jax.Array) -> jax.Array:
    return signs.astype(jnp.float32) * scale
