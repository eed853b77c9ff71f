"""Causal flash attention for training: the score tiles stay in VMEM.

One forward kernel and one backward kernel behind a ``custom_vjp``.

- Forward, one grid step per ``(batch, head group, query block)``: the
  whole sequence's keys and values of the group sit in VMEM, and the kernel
  walks the key blocks up to the diagonal with a running row max and sum
  (blocks above the diagonal are never visited).  It writes the output and
  each row's log-sum-exp.
- Backward, one grid step per ``(batch, head group, key block)``: the
  kernel walks the query blocks from the diagonal down, recomputes each
  probability tile from the saved log-sum-exp, and accumulates dK and dV in
  registers and dQ in a whole-sequence f32 scratch, written once per head
  group.  Neither pass writes a score tile to HBM.

Numerics: exact causal softmax attention with scale ``head_dim ** -0.5``.
Operands are the caller's dtype (bfloat16 in training); the scores come
f32 from the MXU's accumulator, and the softmax statistics and every
accumulator are f32.

Layout: ``q``, ``k``, ``v`` and the output are ``(B, S, H * head_dim)``,
the layout the projections produce, so no transpose runs around the
kernels.  A grid step takes one 128-lane group of heads (two heads of 64,
or one head of 128 or more); each head's scores contract its own lanes
(the group's other lanes are zeroed in the operand), and each head's
results are kept on its own lanes.  :func:`supports` says which shapes the
kernels take; the tile edge follows from ``S`` (:func:`block_size`).

Every Pallas call sits under the name scope ``attention_kernel``, in the
forward and in the backward rule, so a device trace can tell the kernels
from the ops around them.  Off the chip (``common.INTERPRET``) the kernels
are interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import INTERPRET, LANES

MIN_BLOCK = 128          # the smallest tile edge along the sequence
# the backward holds the group's whole-sequence queries, output gradients
# (double-buffered), dQ and its f32 scratch in VMEM: about 16 B x S x lanes,
# so S x lanes <= 2**19 (S = 4096 at 128 lanes) keeps it near 8 MiB, inside
# the 16 MiB scoped VMEM of a TPU v5e
MAX_SEQ_LANES = 1 << 19
KERNEL_SCOPE = "attention_kernel"
_NT = (((1,), (1,)), ((), ()))  # contract the last dims: a @ b.T
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _lanes(num_heads: int, head_dim: int) -> int | None:
    """Lanes of one head group, or ``None`` where heads cannot be grouped
    into whole 128-lane tiles."""
    if head_dim % LANES == 0:
        return head_dim
    if LANES % head_dim == 0 and num_heads % (LANES // head_dim) == 0:
        return LANES
    return None


def supports(seq: int, num_heads: int, head_dim: int) -> bool:
    """Whether the kernels take this shape: whole tiles of
    :data:`MIN_BLOCK` along the sequence, heads that fill whole 128-lane
    groups, and a sequence whose backward fits VMEM."""
    lanes = _lanes(num_heads, head_dim)
    return (seq % MIN_BLOCK == 0 and lanes is not None
            and seq * lanes <= MAX_SEQ_LANES)


def block_size(seq: int) -> int:
    """The tile edge along the sequence: the largest of 512, 256 and 128
    that divides ``seq`` (512 was the fastest at seq 1024 on a TPU v5e)."""
    if seq % MIN_BLOCK:
        raise ValueError(f"seq must be a multiple of {MIN_BLOCK}, got {seq}")
    return next(b for b in (512, 256, 128) if seq % b == 0)


def _head_of_lane(lanes: int, head_dim: int):
    return lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // head_dim


def _only(x, lane_head, h, g):
    """``x`` with the lanes of heads other than ``h`` of the group zeroed."""
    return x if g == 1 else jnp.where(lane_head == h, x, jnp.zeros_like(x))


def _widen(x, lanes):
    """A ``(t, 128)`` lane-replicated column as ``(t, lanes)``."""
    return x[:, :lanes] if lanes <= LANES else jnp.tile(x, (1, lanes // LANES))


def _causal(s, *, keys_on_rows: bool):
    """Mask the diagonal tile: a query sees keys at or before it."""
    row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seen = row <= col if keys_on_rows else col <= row
    return jnp.where(seen, s, _MASKED)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                t, g, head_dim, scale):
    i = pl.program_id(2)
    lanes = q_ref.shape[-1]
    lane_head = _head_of_lane(lanes, head_dim)
    q = q_ref[...]
    qs = [_only(q, lane_head, h, g) for h in range(g)]
    m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
    l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def visit(j, diagonal: bool):
        rows = pl.ds(pl.multiple_of(j * t, t), t)
        k, v = k_ref[rows, :], v_ref[rows, :]
        for h in range(g):
            s = lax.dot_general(qs[h], k, _NT,
                                preferred_element_type=jnp.float32) * scale
            if diagonal:
                s = _causal(s, keys_on_rows=False)
            m_prev = m_sc[h]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
            p = jnp.exp(s - jnp.tile(m_next, (1, t // LANES)))
            alpha = jnp.exp(m_prev - m_next)
            l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=1)[:, None]
            m_sc[h] = m_next
            pv = lax.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            acc_sc[h] = acc_sc[h] * _widen(alpha, lanes) + pv

    @pl.loop(0, i)
    def _(j):
        visit(j, False)

    visit(i, True)
    out = jnp.zeros((t, lanes), jnp.float32)
    for h in range(g):
        l = l_sc[h]
        # each head's output lies on its own lanes of its accumulator
        out = out + _only(acc_sc[h] * _widen(1.0 / l, lanes), lane_head, h, g)
        lse_ref[h:h + 1, :] = (m_sc[h] + jnp.log(l)).T[:1, :]
    o_ref[...] = out.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, *, t, n, g, head_dim, scale):
    j = pl.program_id(2)
    lanes = k_ref.shape[-1]
    lane_head = _head_of_lane(lanes, head_dim)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    k, v = k_ref[...], v_ref[...]
    ks = [_only(k, lane_head, h, g) for h in range(g)]
    vs = [_only(v, lane_head, h, g) for h in range(g)]

    def visit(i, diagonal: bool, carry):
        dk, dv = carry
        cols = pl.ds(pl.multiple_of(i * t, t), t)
        q, do = q_ref[cols, :], do_ref[cols, :]
        dq = jnp.zeros((t, lanes), jnp.float32)
        for h in range(g):
            # keys on the rows, queries on the columns: the saved row
            # statistics broadcast down the sublanes
            s = lax.dot_general(ks[h], q, _NT,
                                preferred_element_type=jnp.float32) * scale
            if diagonal:
                s = _causal(s, keys_on_rows=True)
            p = jnp.exp(s - lse_ref[h:h + 1, cols])
            dp = lax.dot_general(vs[h], do, _NT,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - di_ref[h:h + 1, cols])
            dv = dv + _only(lax.dot(p.astype(do.dtype), do,
                                    preferred_element_type=jnp.float32),
                            lane_head, h, g)
            dk = dk + _only(lax.dot(ds.astype(q.dtype), q,
                                    preferred_element_type=jnp.float32),
                            lane_head, h, g)
            dq = dq + _only(lax.dot(ds.T.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32),
                            lane_head, h, g)
        dq_sc[cols, :] += dq
        return dk, dv

    zero = jnp.zeros((t, lanes), jnp.float32)
    carry = visit(j, True, (zero, zero))
    dk, dv = lax.fori_loop(j + 1, n, lambda i, c: visit(i, False, c), carry)
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(j == n - 1)
    def _():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _geometry(q, num_heads):
    B, S, width = q.shape
    head_dim = width // num_heads
    lanes = _lanes(num_heads, head_dim)
    return B, S, head_dim, lanes, lanes // head_dim, width // lanes


def _spec(shape, index):
    return pl.BlockSpec((None,) + shape, index)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, num_heads: int, t: int, interpret: bool):
    B, S, head_dim, lanes, g, groups = _geometry(q, num_heads)
    kernel = functools.partial(_fwd_kernel, t=t, g=g, head_dim=head_dim,
                               scale=head_dim ** -0.5)
    seq = _spec((S, lanes), lambda b, c, i: (b, 0, c))
    with jax.named_scope(KERNEL_SCOPE):
        o, lse = pl.pallas_call(
            kernel,
            grid=(B, groups, S // t),
            in_specs=[_spec((t, lanes), lambda b, c, i: (b, i, c)), seq, seq],
            out_specs=[_spec((t, lanes), lambda b, c, i: (b, i, c)),
                       pl.BlockSpec((None, None, g, t),
                                    lambda b, c, i: (b, c, 0, i))],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct((B, groups, g, S), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((g, t, LANES), jnp.float32),
                            pltpu.VMEM((g, t, LANES), jnp.float32),
                            pltpu.VMEM((g, t, lanes), jnp.float32)],
            compiler_params=_params(),
            interpret=interpret,
        )(q, k, v)
    return o, (q, k, v, o, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, num_heads: int, t: int, interpret: bool):
    return _forward(q, k, v, num_heads, t, interpret)[0]


def _attention_bwd(num_heads: int, t: int, interpret: bool, res, do):
    q, k, v, o, lse = res
    B, S, head_dim, lanes, g, groups = _geometry(q, num_heads)
    # each row's output . output gradient, laid out like the log-sum-exp
    di = jnp.einsum("bshd,bshd->bhs",
                    o.reshape(B, S, num_heads, head_dim).astype(jnp.float32),
                    do.reshape(B, S, num_heads, head_dim).astype(jnp.float32))
    di = di.reshape(B, groups, g, S)
    kernel = functools.partial(_bwd_kernel, t=t, n=S // t, g=g,
                               head_dim=head_dim, scale=head_dim ** -0.5)
    seq = _spec((S, lanes), lambda b, c, j: (b, 0, c))
    block = _spec((t, lanes), lambda b, c, j: (b, j, c))
    stats = pl.BlockSpec((None, None, g, S), lambda b, c, j: (b, c, 0, 0))
    with jax.named_scope(KERNEL_SCOPE):
        dq, dk, dv = pl.pallas_call(
            kernel,
            grid=(B, groups, S // t),
            in_specs=[seq, block, block, seq, stats, stats],
            out_specs=[seq, block, block],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v)],
            scratch_shapes=[pltpu.VMEM((S, lanes), jnp.float32)],
            compiler_params=_params(),
            interpret=interpret,
        )(q, k, v, do, lse, di)
    return dq, dk, dv


_attention.defvjp(_forward, _attention_bwd)


def causal_attention(q, k, v, *, num_heads: int,
                     interpret: bool | None = None) -> jax.Array:
    """Causal softmax attention of ``(B, S, num_heads * head_dim)`` queries,
    keys and values (head-major within the last axis, as the projections lay
    them out); returns the same layout in ``q``'s dtype."""
    interpret = INTERPRET if interpret is None else interpret
    B, S, width = q.shape
    if not supports(S, num_heads, width // num_heads):
        raise ValueError(f"no kernel for seq {S}, {num_heads} heads of "
                         f"{width // num_heads}")
    return _attention(q, k, v, num_heads, block_size(S), interpret)
