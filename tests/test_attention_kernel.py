"""The fused causal attention kernel (``kernels/flash_attention.py``) against
float32 attention and against the q-chunked scan of ``attn_train``, in
Pallas's interpreter; and the dispatch rule that picks between them
(``models/attention.py::takes_flash``).

Interpreting the kernels is slow (seconds a call), so the shapes are small:
batch 1, 2 heads of 64, seq 256 in bfloat16.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.kernels import common
from repro.kernels import flash_attention as fa
from repro.models import attention
from repro.models.layers import rope

B, H, S, HD = 1, 2, 256, 64


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _f32_attention(q, k, v, num_heads):
    """Causal softmax attention in float32 of ``(B, S, H * hd)`` operands."""
    B, S, width = q.shape
    q, k, v = (t.astype(jnp.float32).reshape(B, S, num_heads, -1)
               for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=lax.Precision.HIGHEST)
    return o.reshape(B, S, width)


@pytest.mark.parametrize("heads,head_dim,tile", [
    (H, HD, None), (H, HD, 128), (1, 128, 128), (4, 32, 128)],
    ids=["from_shape", "tiles_of_128", "head_of_128", "four_heads_of_32"])
def test_kernel_matches_f32_attention(heads, head_dim, tile):
    """Output and the gradients w.r.t. q, k, v, interpreted, against
    float32 attention of the same bfloat16 operands: one whole tile (the
    edge :func:`block_size` picks at seq 256) and 2 x 2 tiles of 128, where
    the tile above the diagonal is skipped, for two heads of 64 to a lane
    group; one head of 128; four heads of 32."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(key, (B, S, heads * head_dim),
                                     jnp.bfloat16) for key in keys)
    if tile is None:
        fn = lambda q, k, v: fa.causal_attention(q, k, v, num_heads=heads,
                                                 interpret=True)
    else:
        fn = lambda q, k, v: fa._attention(q, k, v, heads, tile, True)
    o, pull = jax.vjp(fn, q, k, v)
    o_ref, pull_ref = jax.vjp(lambda *a: _f32_attention(*a, heads), q, k, v)
    assert o.dtype == jnp.bfloat16 and o.shape == q.shape
    got = (o, *pull(do))
    want = (o_ref, *pull_ref(do.astype(jnp.float32)))
    gaps = [_rel(g, w) for g, w in zip(got, want)]
    # bfloat16 outputs: half an ulp is 2e-3 of a value
    assert max(gaps) < 6e-3, gaps


def _cfg(**kw):
    base = dict(name="tiny", family="dense", num_layers=1, d_model=128, num_heads=H,
                num_kv_heads=H, head_dim=HD, d_ff=256, vocab_size=128,
                compute_dtype="bfloat16", attn_chunk=64)
    return ArchConfig(**{**base, **kw})


def _layer(cfg, fused: bool, remat: bool, monkeypatch):
    """``sum(attn_train(...) * dy)``'s output and its gradients w.r.t. the
    input and the four projection weights, on the path ``fused`` picks."""
    monkeypatch.setattr(attention, "takes_flash", lambda *a: fused)
    kp, kx, kd = jax.random.split(jax.random.PRNGKey(1), 3)
    params = attention.attn_init(kp, cfg, jnp.float32)
    x = jax.random.normal(kx, (B, S, cfg.d_model), jnp.float32)
    dy = jax.random.normal(kd, (B, S, cfg.d_model), jnp.float32)
    f = lambda p, x: attention.attn_train(p, x, cfg).astype(jnp.float32)
    if remat:
        f = jax.checkpoint(f, prevent_cse=False)
    y, pull = jax.vjp(f, params, x)
    gp, gx = pull(dy)
    return {"y": y, "dx": gx, **{f"d{k}": v for k, v in gp.items()}}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_layer_on_the_kernel_matches_the_scan(remat, monkeypatch):
    """``attn_train`` with the interpreted kernel against the q-chunked scan
    (chunks of 64), both in bfloat16, and each against the scan in float32:
    the kernel's gaps stay at the scan's, for the output and the gradients
    w.r.t. x, wq, wk, wv and wo; ``remat`` runs the layer under
    ``jax.checkpoint``, as the model does."""
    cfg = _cfg()
    fused = _layer(cfg, True, remat, monkeypatch)
    scan = _layer(cfg, False, remat, monkeypatch)
    want = _layer(dataclasses.replace(cfg, compute_dtype="float32"), False,
                  remat, monkeypatch)
    assert set(fused) == {"y", "dx", "dwq", "dwk", "dwv", "dwo"}
    for name in fused:
        to_scan = _rel(fused[name], scan[name])
        own, scans = _rel(fused[name], want[name]), _rel(scan[name],
                                                         want[name])
        assert to_scan < 1e-2, (name, to_scan)
        assert own < 1.25 * scans + 1e-3, (name, own, scans)


def test_block_size_follows_the_sequence():
    assert [fa.block_size(n) for n in (128, 256, 384, 1024, 4096)] == [
        128, 256, 128, 512, 512]
    with pytest.raises(ValueError):
        fa.block_size(1000)


@pytest.mark.parametrize("seq,heads,head_dim,want", [
    (1024, 12, 64, True), (1024, 16, 64, True), (4096, 8, 128, True),
    (1000, 12, 64, False),    # not whole tiles
    (1024, 3, 64, False),     # two heads of 64 to a lane group
    (1024, 8, 96, False),     # heads that fill no whole lane group
    (8192, 12, 64, False),    # the backward would outgrow VMEM
], ids=["gpt2", "qwen", "head_128", "odd_seq", "odd_heads", "hd_96",
        "long"])
def test_supports(seq, heads, head_dim, want):
    assert fa.supports(seq, heads, head_dim) is want
    if not want:
        x = jnp.zeros((1, seq, heads * head_dim), jnp.bfloat16)
        with pytest.raises(ValueError):
            fa.causal_attention(x, x, x, num_heads=heads, interpret=True)


@pytest.mark.parametrize("arch,seq,window,on_chip,want", [
    ("gpt2-paper", 1024, 0, True, True),
    ("qwen1.5-0.5b", 1024, 0, True, True),
    ("gpt2-paper", 1024, 0, False, False),        # off the chip
    ("gpt2-paper", 1000, 0, True, False),         # S not whole tiles
    ("gpt2-paper", 1024, 256, True, False),       # sliding window
    ("gemma2-27b", 1024, 0, True, False),         # logit softcap
    ("mistral-large-123b", 1024, 0, True, False),  # GQA
], ids=["gpt2-paper", "qwen", "cpu", "odd_seq", "window", "softcap", "gqa"])
def test_takes_flash(arch, seq, window, on_chip, want, monkeypatch):
    cfg = get_config(arch)
    monkeypatch.setattr(common, "INTERPRET", not on_chip)
    assert attention.takes_flash(cfg, seq, window) is want


def test_the_rule_sees_the_properties_it_names():
    # the configurations the rule names by property really have them
    assert get_config("gemma2-27b").attn_softcap > 0
    mistral = get_config("mistral-large-123b")
    assert mistral.num_kv_heads < mistral.num_heads
    for arch in ("gpt2-paper", "qwen1.5-0.5b"):
        c = get_config(arch)
        assert c.num_kv_heads == c.num_heads and c.attn_softcap == 0


def _scan_attn_train(params, x, cfg, *, window: int = 0):
    """The q-chunked ``attn_train`` as it was before the fused kernel, kept
    verbatim to pin the fallback path."""
    B, S, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    q, k, v = attention._qkv(params, x, cfg)
    positions = jnp.arange(S)[None, :]
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = q.reshape(B, S, K, G, hd)

    chunk = min(cfg.attn_chunk, S)
    if S % chunk != 0:
        chunk = S
    n_chunks = S // chunk
    t_idx = jnp.arange(S)

    def body(carry, qc_and_off):
        qc, off = qc_and_off
        q_idx = off * chunk + jnp.arange(chunk)
        m = t_idx[None, :] <= q_idx[:, None]
        if window > 0:
            m &= t_idx[None, :] > (q_idx[:, None] - window)
        m = m[None, None, None]
        out = attention._scores_softmax_value(qc, k, v, m, cfg)
        return carry, out

    with jax.named_scope("attention"):
        q_chunks = q.reshape(B, n_chunks, chunk, K, G, hd).transpose(
            1, 0, 2, 3, 4, 5)
        _, outs = lax.scan(body, (), (q_chunks, jnp.arange(n_chunks)))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H * hd)
    cd = jnp.dtype(cfg.compute_dtype)
    return jnp.einsum("bsh,hd->bsd", out, params["wo"].astype(cd))


@pytest.mark.parametrize("case", ["cpu", "odd_seq", "window", "softcap",
                                  "gqa"])
def test_the_fallback_is_the_scan_unchanged(case, monkeypatch):
    """Where the rule says no, ``attn_train`` and its gradient trace to
    exactly the scan's program, with no kernel in it."""
    cfg, seq, window = _cfg(), S, 0
    monkeypatch.setattr(common, "INTERPRET", case == "cpu")
    if case == "odd_seq":
        seq = 200
    elif case == "window":
        window = 64
    elif case == "softcap":
        cfg = _cfg(attn_softcap=50.0)
    elif case == "gqa":
        cfg = _cfg(num_kv_heads=1)
    assert not attention.takes_flash(cfg, seq, window)
    params = jax.eval_shape(
        lambda k: attention.attn_init(k, cfg, jnp.float32),
        jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((B, seq, cfg.d_model), jnp.float32)

    def traced(fn):
        loss = lambda p, x: jnp.sum(fn(p, x, cfg, window=window))
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x))

    got = traced(attention.attn_train)
    assert got == traced(_scan_attn_train)
    assert "pallas_call" not in got
