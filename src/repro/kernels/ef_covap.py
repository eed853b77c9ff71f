"""Fused COVAP error-feedback update kernel — the compression hot-spot.

One HBM pass computes, per bucket:

    t    = g + coeff * r
    send = t        if the bucket is selected this phase else 0
    r'   = 0        if selected                           else t

The reference path (core/compressors/covap.py) does this with 2-3 separate
elementwise ops (2-3 HBM round trips over the gradient); fusing makes
compression overhead a single streaming pass — the structural version of
the paper's "near-zero compression overhead" claim.

Layout: buckets are flat vectors, viewed as (rows, 128) lanes and tiled
in (block_rows, 128) blocks (``common.lane_call``); the coefficient is an
SMEM scalar; grid is 1-D over blocks; ``selected`` is a *static* kernel
specialisation (the coarse filter is static per phase, SS III.A).

Rounding note: where the fused single pass compiles ``g + c*r`` to an FMA
(one rounding) and the 2-op jnp reference rounds the product separately,
results are ~1 ulp MORE accurate but not bitwise-identical to
``kernels.ref.ef_update_ref``.  (On a TPU v5e neither form contracts: there
the compiled kernel matched the reference bitwise on a 6,553,601-element
bucket.)  The segmented execute path therefore
engages this kernel on TPU by default and on CPU only via the explicit
``use_ef_kernel=True`` compressor option (tests/benchmarks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ELEMWISE_BLOCK, INTERPRET, lane_call


def _kernel_selected(g_ref, r_ref, coeff_ref, send_ref, rnew_ref):
    c = coeff_ref[0].astype(g_ref.dtype)
    t = g_ref[...] + c * r_ref[...]
    send_ref[...] = t
    rnew_ref[...] = jnp.zeros_like(t)


def _kernel_unselected(g_ref, r_ref, coeff_ref, send_ref, rnew_ref):
    c = coeff_ref[0].astype(g_ref.dtype)
    t = g_ref[...] + c * r_ref[...]
    send_ref[...] = jnp.zeros_like(t)
    rnew_ref[...] = t


@functools.partial(jax.jit, static_argnames=("selected", "block", "interpret"))
def ef_update(
    g: jax.Array,
    r: jax.Array,
    coeff: jax.Array,
    *,
    selected: bool,
    block: int = ELEMWISE_BLOCK,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """g, r: flat (N,) bucket; coeff: scalar.  Returns (send, r_new)."""
    interpret = INTERPRET if interpret is None else interpret
    assert g.ndim == 1 and g.shape == r.shape
    # SMEM holds 32-bit words: the coefficient travels as f32 and is cast
    # to the gradient dtype inside the kernel (what the 2-op form computes)
    coeff_arr = jnp.asarray(coeff, jnp.float32).reshape(1)
    kernel = _kernel_selected if selected else _kernel_unselected
    return lane_call(
        kernel, (g, r), (g.dtype, r.dtype),
        block=block, interpret=interpret, smem_in=(coeff_arr,),
    )
