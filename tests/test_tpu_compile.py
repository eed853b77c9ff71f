"""Compile the main-path Pallas kernels and one full-width COVAP step for a
described TPU v5e chip (nothing runs: the chip is described, not attached).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, so
the worker that is given this file loads it and every other worker still
collects the same tests.  Each compile passes ``interpret=False`` (or patches
``INTERPRET``), because off the chip the kernels would otherwise be
interpreted and the compile would prove nothing about the Mosaic lowering.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ef_covap import ef_update
from repro.kernels.flash_attention import causal_attention
from repro.kernels.pack_ef_cast import pack_ef_cast
from repro.kernels.quantize import dequantize_fp8, quantize_fp8
from repro.kernels.sign_compress import sign_compress
from repro.kernels.topk_threshold import threshold_filter

# one 25 MiB DDP bucket of f32 gradients, plus one element so the tail
# block is ragged
N = 25 * 1024 * 1024 // 4 + 1
V5E_HBM_BYTES = 16 * 1000**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    "ef_update-selected": (
        lambda g, r, c: ef_update(g, r, c, selected=True, interpret=False),
        ("f32", "f32", "scalar"),
    ),
    "ef_update-unselected": (
        lambda g, r, c: ef_update(g, r, c, selected=False, interpret=False),
        ("f32", "f32", "scalar"),
    ),
    "pack_ef_cast-f32": (
        lambda g, r, c: pack_ef_cast(
            g, r, c, selected=True, interpret=False
        ),
        ("f32", "f32", "scalar"),
    ),
    "pack_ef_cast-bf16": (
        lambda g, r, c: pack_ef_cast(
            g, r, c, selected=True, wire_dtype="bfloat16", interpret=False
        ),
        ("f32", "f32", "scalar"),
    ),
    "sign_compress": (
        lambda x: sign_compress(x, interpret=False), ("f32",),
    ),
    "threshold_filter": (
        lambda x, t: threshold_filter(x, t, interpret=False),
        ("f32", "scalar"),
    ),
    "quantize_fp8": (lambda x: quantize_fp8(x, interpret=False), ("f32",)),
    "dequantize_fp8": (
        lambda q, s: dequantize_fp8(q, s, interpret=False),
        ("fp8", "scales"),
    ),
}


# gpt2-paper's attention at seq 1024 and batch 8: 12 heads of 64
ATTN_SHAPE, ATTN_HEADS = (8, 1024, 12 * 64), 12


def _attention_fwd(q, k, v):
    return causal_attention(q, k, v, num_heads=ATTN_HEADS, interpret=False)


def _attention_fwd_bwd(q, k, v):
    o, pull = jax.vjp(_attention_fwd, q, k, v)
    return pull(o)


def _kernel_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("fn", [_attention_fwd, _attention_fwd_bwd],
                         ids=["fwd", "fwd_bwd"])
def test_flash_attention_lowers_for_v5e(fn, one_chip):
    """The fused causal attention kernels at gpt2-paper's width: the
    forward is one kernel, the backward one more."""
    arg = _spec(ATTN_SHAPE, jnp.bfloat16, one_chip)
    text = jax.jit(fn).lower(arg, arg, arg).compile().as_text()
    assert _kernel_calls(text) == (1 if fn is _attention_fwd else 2)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_lowers_for_v5e(name, one_chip):
    fn, kinds = KERNELS[name]
    shapes = {
        "f32": ((N,), jnp.float32),
        "scalar": ((), jnp.float32),
        "fp8": ((N,), jnp.float8_e4m3fn),
        "scales": ((-(-N // 8192),), jnp.float32),
    }
    args = [_spec(*shapes[k], one_chip) for k in kinds]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arena", [False, True], ids=["post", "arena"])
def test_gpt2_paper_covap_phase_step_fits_one_v5e(arena, one_chip, monkeypatch):
    """The full-width gpt2-paper phase-0 COVAP step at seq 1024 and batch 8
    compiles for one v5e chip with the fused EF kernel (``ef_update``, or
    ``pack_ef_cast`` on the arena path) and the fused attention kernels
    inside, and fits its HBM."""
    from repro.configs import get_config
    from repro.core import build_plan
    from repro.kernels import common, ef_covap, flash_attention
    from repro.kernels import pack_ef_cast as pack
    from repro.models import attention, build_model
    from repro.optim import adamw
    from repro.train.trainer import TrainConfig, build_train_step, make_compressor

    # off the chip the backend says CPU: force the kernel path, compiled
    monkeypatch.setattr(common, "INTERPRET", False)
    monkeypatch.setattr(ef_covap, "INTERPRET", False)
    monkeypatch.setattr(pack, "INTERPRET", False)
    monkeypatch.setattr(flash_attention, "INTERPRET", False)
    cfg = get_config("gpt2-paper")
    assert attention.takes_flash(cfg, 1024, 0)
    model = build_model(cfg)
    opt = adamw(1e-4)
    tc = TrainConfig(compressor="covap", interval=4, arena=arena)
    comp = make_compressor(tc)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    plan = build_plan(params, bucket_bytes=tc.bucket_bytes,
                      max_buckets=tc.max_buckets, interval=tc.interval)
    opt_state = jax.eval_shape(opt.init, params)
    comp_state = jax.eval_shape(lambda p: comp.init_state(p, plan), params)
    batch = {
        k: jax.ShapeDtypeStruct((8, 1024), jnp.int32)
        for k in ("tokens", "labels")
    }
    place = lambda tree: jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip), tree
    )
    step = build_train_step(model, opt, comp, plan, phase=0, donate=False)
    compiled = step.lower(
        place(params), place(opt_state), place(comp_state), place(batch),
        _spec((), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the layer scans compile one body each: the forward kernel, and its
    # remat recompute with the backward kernel
    attention_calls = [line for line in text.splitlines()
                       if 'custom_call_target="tpu_custom_call"' in line
                       and "attention_kernel" in line]
    assert len(attention_calls) == 3
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
