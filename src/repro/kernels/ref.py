"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8_MAX = 448.0


def cast_error(t, wire_dtype):
    """``(w, t - w)`` with ``w = t.astype(wire_dtype)``: the wire value and
    the rounding error it left behind, exact.

    Under XLA's default excess-precision rule the TPU backend may skip the
    narrowing cast when its result is widened again inside one fusion; on a
    v5e ``t - t.astype(bf16).astype(f32)`` came out all zeros, so error
    feedback lost the cast error.  The barrier makes ``w`` a real narrow
    value before it is widened."""
    w = t.astype(wire_dtype)
    return w, t - jax.lax.optimization_barrier(w).astype(t.dtype)


def ef_update_ref(g, r, coeff, *, selected: bool):
    t = g + jnp.asarray(coeff, g.dtype) * r
    if selected:
        return t, jnp.zeros_like(t)
    return jnp.zeros_like(t), t


def pack_ef_cast_ref(g, r, coeff, *, selected: bool, wire_dtype=None):
    """Fused pack + error feedback + wire cast (arena pack pass).

    ``t = g + coeff * r`` (``r=None`` -> ``t = g``); for a *selected*
    bucket the wire value is ``t`` cast to ``wire_dtype`` (identity when
    ``None``) and the residual is the quantisation error ``t - cast(t)``
    (zero without a cast); an *unselected* bucket sends nothing and keeps
    the whole compensated gradient as its residual.

    Every expression matches the legacy segmented path
    (``stages.WireCast.execute_segment`` + ``stages.SyncPipeline._ef_segment``)
    op-for-op — including the ``coeff * r.astype(g.dtype)`` promotion and
    the ``coeff=None`` classic-EF plain add — so the jnp fallback is
    bitwise-identical to arena-off.  Returns ``(wire, r_new)``; ``r_new``
    is ``None`` when ``r`` is.
    """
    if r is None:
        t = g
    elif coeff is None:
        t = g + r.astype(g.dtype)
    else:
        t = g + coeff * r.astype(g.dtype)
    wd = jnp.dtype(wire_dtype) if wire_dtype is not None else None
    if not selected:
        zero = jnp.zeros_like(t if wd is None else t.astype(wd))
        return zero, (t if r is not None else None)
    if wd is None or t.dtype == wd:
        return t, (jnp.zeros_like(t) if r is not None else None)
    w, rnew = cast_error(t, wd)
    return w, (rnew if r is not None else None)


def quantize_fp8_ref(x, *, block: int = 8192):
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, (0, pad)).astype(jnp.float32)
    nb = xp.shape[0] // block
    x2 = xp.reshape(nb, block)
    amax = jnp.max(jnp.abs(x2), axis=1)
    scales = jnp.maximum(amax / FP8_MAX, 1e-12)
    q = (x2 / scales[:, None]).astype(jnp.float8_e4m3fn)
    return q.reshape(-1)[:n], scales


def dequantize_fp8_ref(q, scales, *, block: int = 8192):
    n = q.shape[0]
    pad = (-n) % block
    qp = jnp.pad(q, (0, pad))
    nb = qp.shape[0] // block
    x = qp.reshape(nb, block).astype(jnp.float32) * scales[:, None]
    return x.reshape(-1)[:n]


def sign_compress_ref(x):
    signs = jnp.where(x >= 0, 1, -1).astype(jnp.int8)
    scale = jnp.mean(jnp.abs(x.astype(jnp.float32)))
    return signs, scale


def threshold_filter_ref(x, threshold, *, block: int = 32768):
    keep = jnp.abs(x) >= threshold
    y = jnp.where(keep, x, jnp.zeros_like(x))
    n = x.shape[0]
    pad = (-n) % block
    kp = jnp.pad(keep, (0, pad))
    counts = kp.reshape(-1, block).sum(axis=1).astype(jnp.int32)
    return y, counts


def matmul_ref(a, b, out_dtype=jnp.float32):
    return jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(out_dtype)
