"""Attention: MHA/GQA/MQA with RoPE, q-chunked streaming softmax (bounded
memory at 32k prefill), sliding-window and softcap variants, and a KV-cache
decode path (rolling cache for windowed layers -> O(window) state at 500k).
On the chip, full causal MHA layers run a fused flash-attention kernel
(``kernels/flash_attention.py``) in place of the q-chunked scan.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import common, flash_attention
from .layers import linear_init, rope, softcap, truncated_normal_init

NEG_INF = -2.0e38


def attn_init(key, cfg, dtype) -> dict:
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": truncated_normal_init(ks[0], (d, H * hd), dtype),
        "wk": truncated_normal_init(ks[1], (d, K * hd), dtype),
        "wv": truncated_normal_init(ks[2], (d, K * hd), dtype),
        "wo": truncated_normal_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def _qkv(params, x, cfg):
    cd = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cd)
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dh->bsh", xc, params["wq"].astype(cd))
    k = jnp.einsum("bsd,dh->bsh", xc, params["wk"].astype(cd))
    v = jnp.einsum("bsd,dh->bsh", xc, params["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cd)
        k = k + params["bk"].astype(cd)
        v = v + params["bv"].astype(cd)
    return (
        q.reshape(B, S, H, hd),
        k.reshape(B, S, K, hd),
        v.reshape(B, S, K, hd),
    )


def _scores_softmax_value(q, k, v, mask, cfg):
    """q: (B,Sq,K,G,hd)  k/v: (B,T,K,hd)  mask: (B,1,1,Sq,T) or (1,1,1,Sq,T).

    Returns (B,Sq,K,G,hd).  fp32 softmax."""
    scale = cfg.head_dim ** -0.5
    s = jnp.einsum("bqkgh,btkh->bkgqt", q, k).astype(jnp.float32) * scale
    if cfg.attn_softcap > 0:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqt,btkh->bqkgh", p, v)


def takes_flash(cfg, seq: int, window: int) -> bool:
    """Whether :func:`attn_train` runs the fused causal kernel: on the chip,
    for full causal attention with as many key/value heads as query heads,
    no logit softcap, and a shape the kernel takes (a sequence of whole
    128-row tiles; ``flash_attention.supports``).  Every other layer (and
    every run off the chip) takes the q-chunked scan."""
    return (not common.INTERPRET and window == 0 and cfg.attn_softcap == 0
            and cfg.num_kv_heads == cfg.num_heads
            and flash_attention.supports(seq, cfg.num_heads, cfg.head_dim))


def attn_train(params, x, cfg, *, window: int = 0) -> jax.Array:
    """Causal self-attention over a full sequence: the fused kernel where
    :func:`takes_flash` allows it, else q-chunked.

    ``window > 0`` restricts to a sliding window (j in (i-window, i])."""
    B, S, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    cd = jnp.dtype(cfg.compute_dtype)
    q, k, v = _qkv(params, x, cfg)
    positions = jnp.arange(S)[None, :]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if takes_flash(cfg, S, window):
        with jax.named_scope("attention"):
            out = flash_attention.causal_attention(
                *(t.reshape(B, S, H * hd) for t in (q, k, v)), num_heads=H)
        return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(cd))
    q = q.reshape(B, S, K, G, hd)

    chunk = min(cfg.attn_chunk, S)
    if S % chunk != 0:
        chunk = S  # fall back to unchunked for odd smoke shapes
    n_chunks = S // chunk
    t_idx = jnp.arange(S)

    def body(carry, qc_and_off):
        qc, off = qc_and_off
        q_idx = off * chunk + jnp.arange(chunk)
        m = t_idx[None, :] <= q_idx[:, None]
        if window > 0:
            m &= t_idx[None, :] > (q_idx[:, None] - window)
        m = m[None, None, None]  # (1,1,1,chunk,T)
        out = _scores_softmax_value(qc, k, v, m, cfg)
        return carry, out

    # the scope holds what a fused attention kernel would replace: the
    # chunked scores, softmax and value product with their layout moves;
    # the QKV and output projections stay outside it
    with jax.named_scope("attention"):
        q_chunks = q.reshape(B, n_chunks, chunk, K, G, hd).transpose(
            1, 0, 2, 3, 4, 5)
        _, outs = lax.scan(body, (), (q_chunks, jnp.arange(n_chunks)))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H * hd)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(cd))


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------

def _cache_dtype(cfg):
    if cfg.kv_cache_dtype == "int8":
        return jnp.int8
    return jnp.dtype(cfg.kv_cache_dtype or cfg.compute_dtype)


def init_cache(cfg, batch: int, max_len: int, *, window: int = 0) -> dict:
    """Rolling cache for windowed layers; linear cache otherwise.

    With ``kv_cache_dtype='int8'`` keys/values are stored quantised with a
    per-(slot, position, head) fp16-ish scale (SSPerf memory-term lever:
    halves decode HBM traffic vs bf16)."""
    K, hd = cfg.num_kv_heads, cfg.head_dim
    T = min(window, max_len) if window > 0 else max_len
    dt = _cache_dtype(cfg)
    c = {
        "k": jnp.zeros((batch, T, K, hd), dt),
        "v": jnp.zeros((batch, T, K, hd), dt),
    }
    if cfg.kv_cache_dtype == "int8":
        c["k_scale"] = jnp.zeros((batch, T, K), jnp.bfloat16)
        c["v_scale"] = jnp.zeros((batch, T, K), jnp.bfloat16)
    return c


def cache_specs(cfg, batch: int, max_len: int, *, window: int = 0) -> dict:
    K, hd = cfg.num_kv_heads, cfg.head_dim
    T = min(window, max_len) if window > 0 else max_len
    dt = _cache_dtype(cfg)
    c = {
        "k": jax.ShapeDtypeStruct((batch, T, K, hd), dt),
        "v": jax.ShapeDtypeStruct((batch, T, K, hd), dt),
    }
    if cfg.kv_cache_dtype == "int8":
        c["k_scale"] = jax.ShapeDtypeStruct((batch, T, K), jnp.bfloat16)
        c["v_scale"] = jax.ShapeDtypeStruct((batch, T, K), jnp.bfloat16)
    return c


def _quantize_kv(x):
    """x: (B, K, hd) -> (int8 payload, (B, K) scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


def attn_decode(params, x, cache, pos, cfg, *, window: int = 0):
    """One decode step.

    x: (B, 1, d); pos: (B,) absolute position of the new token.
    Returns (y (B,1,d), new_cache)."""
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    T = cache["k"].shape[1]
    q, k, v = _qkv(params, x, cfg)
    q = rope(q, pos[:, None], cfg.rope_theta)
    k = rope(k, pos[:, None], cfg.rope_theta)

    slot = (pos % T) if window > 0 else pos  # rolling for windowed layers
    b_idx = jnp.arange(B)
    quantized = cfg.kv_cache_dtype == "int8"
    if quantized:
        qk, sk = _quantize_kv(k[:, 0])
        qv, sv = _quantize_kv(v[:, 0])
        new_cache = {
            "k": cache["k"].at[b_idx, slot].set(qk),
            "v": cache["v"].at[b_idx, slot].set(qv),
            "k_scale": cache["k_scale"].at[b_idx, slot].set(sk),
            "v_scale": cache["v_scale"].at[b_idx, slot].set(sv),
        }
        new_k = _dequantize_kv(new_cache["k"], new_cache["k_scale"], k.dtype)
        new_v = _dequantize_kv(new_cache["v"], new_cache["v_scale"], v.dtype)
    else:
        new_k = cache["k"].at[b_idx, slot].set(k[:, 0])
        new_v = cache["v"].at[b_idx, slot].set(v[:, 0])
        new_cache = {"k": new_k, "v": new_v}

    t_idx = jnp.arange(T)[None, :]
    if window > 0:
        valid = t_idx <= jnp.minimum(pos, T - 1)[:, None]
    else:
        valid = t_idx <= pos[:, None]
    mask = valid[:, None, None, None, :]  # (B,1,1,1,T)

    qh = q.reshape(B, 1, K, G, hd)
    out = _scores_softmax_value(qh, new_k, new_v, mask, cfg)
    out = out.reshape(B, 1, H * hd)
    cd = jnp.dtype(cfg.compute_dtype)
    y = jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(cd))
    return y, new_cache
