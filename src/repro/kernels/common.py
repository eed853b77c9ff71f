"""Shared Pallas helpers: interpret-mode selection + the lane-tiled layout.

Kernels TARGET TPU (pl.pallas_call with explicit VMEM BlockSpecs, tile sizes
aligned to the 8x128 VPU lanes / 128x128 MXU); on a CPU backend they are
VALIDATED with ``interpret=True`` which executes the kernel body in Python.
``INTERPRET`` auto-detects the backend.

Every streaming (elementwise / per-block) kernel goes through
:func:`lane_call`: a flat ``(N,)`` vector is zero-padded to whole blocks and
viewed as ``(rows, 128)`` lanes, and the grid walks ``(block_rows, 128)``
tiles.  That is the form the Mosaic lowering accepts: the last two block
dims are a multiple of the dtype's sublane count (8 rows for 32-bit, 16 for
16-bit, 32 for 8-bit) and exactly 128 lanes.  Scalars (an EF coefficient, a
threshold) and one-value-per-block side outputs live in SMEM, whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = jax.default_backend() != "tpu"

LANES = 128
# default block: 1024 rows x 128 lanes = 128Ki elements (512 KiB at f32).
# The EF kernels stream two f32 inputs and two outputs, double-buffered:
# 4 x 2 x 512 KiB = 4 MiB of VMEM, a quarter of v5e's 16 MiB scoped default.
ELEMWISE_BLOCK = 1024 * LANES

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def sublanes(*dtypes) -> int:
    """Row multiple of a VMEM tile holding every one of ``dtypes``: 8 for
    32-bit, 16 for 16-bit, 32 for 8-bit values."""
    return max(32 // jnp.dtype(d).itemsize for d in dtypes)


def lane_tiling(n: int, block: int, *dtypes) -> tuple[int, int]:
    """``(rows per grid step, number of blocks)`` for an ``(n,)`` vector
    tiled in blocks of ``block`` elements.  A vector shorter than one block
    gets one block shrunk to the rows it needs (rounded up to the sublane
    count), so a small segment does not pay for a full block of padding."""
    if block % LANES:
        raise ValueError(f"block must be a multiple of {LANES}, got {block}")
    sub = sublanes(*dtypes)
    need = -(-max(n, 1) // LANES)
    rows = min(block // LANES, -(-need // sub) * sub)
    return rows, -(-need // rows)


def lane_call(kernel, arrays, out_dtypes, *, block, interpret,
              smem_in=(), smem_out=()):
    """Run ``kernel`` over the flat, equal-length ``arrays`` in lane tiles.

    ``kernel`` receives ``(*array_refs, *smem_in_refs, *out_refs,
    *smem_out_refs)``.  ``smem_in`` are small arrays passed whole in SMEM (a
    ``(1,)`` scalar, or one value per block read at ``pl.program_id(0)``);
    ``smem_out`` are dtypes of ``(nblocks,)`` outputs held in SMEM, one value
    written per block.  Returns the flat outputs unpadded to ``N``, followed
    by the per-block outputs."""
    n = arrays[0].shape[0]
    dtypes = [a.dtype for a in arrays] + [jnp.dtype(d) for d in out_dtypes]
    rows, nb = lane_tiling(n, block, *dtypes)
    padded = nb * rows * LANES
    tiles = [
        jnp.pad(a, (0, padded - n)).reshape(nb * rows, LANES) for a in arrays
    ]
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[spec] * len(arrays) + [_SMEM] * len(smem_in),
        out_specs=[spec] * len(out_dtypes) + [_SMEM] * len(smem_out),
        out_shape=[
            jax.ShapeDtypeStruct((nb * rows, LANES), d) for d in out_dtypes
        ] + [jax.ShapeDtypeStruct((nb,), d) for d in smem_out],
        interpret=interpret,
    )(*tiles, *smem_in)
    flat = [o.reshape(-1)[:n] for o in outs[: len(out_dtypes)]]
    return (*flat, *outs[len(out_dtypes):])
