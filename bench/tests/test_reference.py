"""The plain reference against the program, on the CPU at small sizes.

The reference imports nothing of the program; these tests may."""
import functools
import json
import os

import jax
import pytest

from bench import correctness, run
from bench.references import decoder
from bench.tests import tiny


def program_plan(shapes_tree, bucket_bytes, max_buckets, interval):
    from repro.core import build_plan

    return build_plan(shapes_tree, bucket_bytes=bucket_bytes,
                      max_buckets=max_buckets, interval=interval)


def ids_from_plan(plan):
    """Per leaf, the bucket of every element, from the program's plan."""
    import numpy as np

    out = [np.full(s, -1, np.int64) for s in plan.leaf_shapes]
    for b in plan.buckets:
        for seg in b.segments:
            idx = [slice(seg.row_lo, seg.row_hi)]
            if seg.sub_axis is not None:
                idx += [slice(None)] * (seg.sub_axis - 1)
                idx.append(slice(seg.sub_lo, seg.sub_hi))
            out[seg.leaf_idx][tuple(idx)] = b.index
    return out


def expand(ids, shape):
    import numpy as np

    ids = ids.reshape(ids.shape + (1,) * (len(shape) - ids.ndim))
    return np.broadcast_to(ids, shape)


@pytest.mark.parametrize("config,bucket_bytes", [
    ("gpt2-paper", decoder.BUCKET_BYTES),
    ("qwen1.5-0.5b", decoder.BUCKET_BYTES),
    ("gpt2-paper", 3 * 1024 * 1024),
    ("qwen1.5-0.5b", 512 * 1024),
])
def test_bucket_ids_match_the_program_plan(config, bucket_bytes):
    import numpy as np

    with open(os.path.join(tiny.ROOT, "bench", "configs",
                           f"{config}.json")) as f:
        arch = json.load(f)["config"]
    shapes = decoder.param_shapes(arch)
    tree = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, "float32"), shapes,
                        is_leaf=lambda x: isinstance(x, tuple))
    plan = program_plan(tree, bucket_bytes, decoder.MAX_BUCKETS, 4)
    ours = decoder.bucket_ids(list(plan.leaf_shapes),
                              bucket_bytes=bucket_bytes,
                              max_buckets=decoder.MAX_BUCKETS, interval=4)
    assert plan.num_buckets > 4
    for want, got, shape in zip(ids_from_plan(plan), ours, plan.leaf_shapes):
        assert (expand(got, shape) == want).all()


def test_parameter_tree_matches_the_model():
    from repro.configs import get_config
    from repro.models import build_model

    cell = tiny.cell()
    arch = cell["config"]["config"]
    model = build_model(get_config("qwen1.5-0.5b").with_(**arch))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: decoder.init_params(arch, 7))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), want) == \
        jax.tree.map(lambda s: (s.shape, s.dtype), got)


@pytest.mark.parametrize("mlp_act,qkv_bias", [("swiglu", True),
                                              ("gelu", False)])
def test_reference_follows_the_program_in_float32(monkeypatch, mlp_act,
                                                  qkv_bias):
    """With the program computing in float32 and buckets small enough that
    the coarse filter splits the model into many, the three steps agree to
    rounding: same model, same bucket selection, same error feedback, same
    AdamW."""
    from repro.train import trainer

    small = 64 * 1024
    monkeypatch.setattr(decoder, "BUCKET_BYTES", small)
    monkeypatch.setattr(trainer, "TrainConfig",
                        functools.partial(trainer.TrainConfig,
                                          bucket_bytes=small))
    cell = tiny.cell(compute_dtype="float32", mlp_act=mlp_act,
                     qkv_bias=qkv_bias)
    r = run.run_cell(cell, 2**31 + 11, 0.2, False, tiny.devices(), tiny.PEAK)
    assert r["correct"]
    for name, c in r["checks"].items():
        assert c["value"] < 1e-4, (name, c)


def test_gaps_by_worst_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 0.0, "d": 4.0}
    prog = {"a": 1.1, "b": 2.0, "c": 0.2, "d": 4.0}
    # median of the nonzero leaves is 2: a reads 0.1/2, c 0.2/2
    assert correctness.worst_leaf(prog, ref, ref) == pytest.approx(0.1)
    assert correctness.worst_leaf(ref, ref, ref) == 0.0
    unchanged = {k: 0.0 for k in ref}
    assert correctness.worst_leaf(unchanged, ref, ref) == pytest.approx(1.0)
