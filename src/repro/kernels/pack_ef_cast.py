"""Fused arena pack + error feedback + wire cast — one streaming pass.

The zero-copy gradient arena (``core/arena.py``) turns every bucket into a
static-offset view of one flat buffer, so the only remaining per-step work
on the compression path is producing that buffer.  The legacy segmented
path materialises three arrays per bucket to do it (the flattened gather,
the compensated ``t = g + c*r``, and the wire-dtype cast); this kernel
fuses them into one HBM pass per segment:

    t    = g + coeff * r
    wire = cast(t)                  if the bucket is selected else 0
    r'   = t - cast(t).astype(f32)  if selected (0 when no cast) else t

``selected`` and the cast target are *static* kernel specialisations (the
coarse filter is static per phase, paper SS III.A), so each compiled phase
contains only the variant it needs.

Layout: flat vectors viewed as (rows, 128) lanes and tiled in
(block_rows, 128) blocks (``common.lane_call``; 16-row multiples where the
wire is 16-bit); the coefficient is an SMEM scalar; grid is 1-D over
blocks.  Two outputs per block (wire value at the wire dtype, residual at
the gradient dtype) stream back to HBM once.

Rounding note (same as ``ef_covap.ef_update``): where the fused pass
compiles ``g + c*r`` to an FMA (single rounding) and the 2-op jnp reference
rounds the product separately, interpret mode cannot be bitwise-identical
to ``kernels.ref.pack_ef_cast_ref``.  The arena path therefore engages this
kernel on TPU by default and on CPU only via the explicit
``use_pack_kernel=True`` compressor option; the CPU default is the ref
formulation, which IS bitwise-identical to the arena-off legacy ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import ELEMWISE_BLOCK, INTERPRET, lane_call


def _kernel_selected_cast(wd):
    def kernel(g_ref, r_ref, coeff_ref, wire_ref, rnew_ref):
        c = coeff_ref[0].astype(g_ref.dtype)
        t = g_ref[...] + c * r_ref[...]
        w = t.astype(wd)
        wire_ref[...] = w
        rnew_ref[...] = t - w.astype(t.dtype)

    return kernel


def _kernel_selected(g_ref, r_ref, coeff_ref, wire_ref, rnew_ref):
    c = coeff_ref[0].astype(g_ref.dtype)
    t = g_ref[...] + c * r_ref[...]
    wire_ref[...] = t
    rnew_ref[...] = jnp.zeros_like(t)


def _kernel_unselected(g_ref, r_ref, coeff_ref, wire_ref, rnew_ref):
    c = coeff_ref[0].astype(g_ref.dtype)
    t = g_ref[...] + c * r_ref[...]
    wire_ref[...] = jnp.zeros_like(wire_ref[...])
    rnew_ref[...] = t


@functools.partial(
    jax.jit, static_argnames=("selected", "wire_dtype", "block", "interpret")
)
def pack_ef_cast(
    g: jax.Array,
    r: jax.Array,
    coeff: jax.Array,
    *,
    selected: bool,
    wire_dtype: str | None = None,
    block: int = ELEMWISE_BLOCK,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """g, r: flat (N,) segment; coeff: scalar.  Returns (wire, r_new) with
    ``wire`` at ``wire_dtype`` (or ``g.dtype`` when None) — the value the
    arena slot receives — and ``r_new`` at ``r``'s dtype."""
    interpret = INTERPRET if interpret is None else interpret
    assert g.ndim == 1 and g.shape == r.shape
    wd = jnp.dtype(wire_dtype) if wire_dtype is not None else jnp.dtype(g.dtype)
    cast = wd != g.dtype
    coeff_arr = jnp.asarray(coeff, jnp.float32).reshape(1)

    if not selected:
        kernel = _kernel_unselected
    elif cast:
        kernel = _kernel_selected_cast(wd)
    else:
        kernel = _kernel_selected
    return lane_call(
        kernel, (g, r), (wd, r.dtype),
        block=block, interpret=interpret, smem_in=(coeff_arr,),
    )
