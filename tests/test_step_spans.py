"""The trainer's own spans on the profiler's clock: the named scopes a phase
step carries in its HLO metadata (which name the device ops of a profile),
and the host spans ``Trainer.run`` opens in a ``jax.profiler`` trace."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.data import DataConfig, make_loader
from repro.models import build_model
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer


def _setup(overlap="post", log_every=10 ** 9):
    cfg = get_reduced("gpt2-paper").with_(vocab_size=256, remat=True)
    tc = TrainConfig(compressor="covap", interval=2, bucket_bytes=1 << 14,
                     max_buckets=16, log_every=log_every, overlap=overlap)
    tr = Trainer(build_model(cfg), adamw(3e-3), tc)
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4,
                    corpus_tokens=1 << 12)
    return tr, state, make_loader(dc)


def _stacks(overlap):
    tr, state, loader = _setup(overlap)
    text = tr._phase_fn(0).lower(
        state["params"], state["opt"], state["comp"], next(iter(loader)),
        jnp.int32(0)).compile().as_text()
    return [s.split("/") for s in re.findall(r'op_name="([^"]*)"', text)]


@pytest.mark.parametrize("overlap", ["post", "fused"])
def test_phase_step_carries_the_model_attention_and_optimizer_scopes(overlap):
    stacks = _stacks(overlap)
    forward = [s for s in stacks if "jvp(model)" in s]
    backward = [s for s in stacks if "transpose(jvp(model))" in s]
    optimizer = [s for s in stacks if "optimizer" in s]
    assert forward and backward and optimizer
    # attention sits inside the model scope, in both passes, and the remat
    # recompute of the backward pass keeps it
    assert any("attention" in s for s in forward)
    assert any("attention" in s for s in backward)
    assert any("rematted_computation" in s and "attention" in s
               for s in backward)
    # the scopes do not nest into each other
    assert not any("jvp(model)" in s or "transpose(jvp(model))" in s
                   for s in optimizer)
    # the per-bucket sync scopes stay outside the model scope, so that no
    # op is read as both sync and backward: after the backward pass (post)
    # or inside it as a transform of their own scope (fused)
    buckets = [s for s in stacks if any("covap_bucket_" in x for x in s)]
    assert buckets
    assert not any("jvp(model)" in s or "transpose(jvp(model))" in s
                   for s in buckets)
    wrapped = any(x.startswith("transpose(jvp(covap_bucket_")
                  for s in buckets for x in s)
    assert wrapped == (overlap == "fused")


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "train_step" or ev.name.startswith("train."):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return out


def test_run_opens_a_step_span_and_host_spans_on_the_profiler_clock(tmp_path):
    tr, state, loader = _setup(log_every=2)
    it = iter(loader)
    tr.run(state, it, steps=1, log=None)      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.run(state, it, steps=3, log=None)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    names = [n for n, *_ in events]
    assert {n: names.count(n) for n in set(names)} == {
        "train_step": 3, "train.batch_wait": 3, "train.dispatch": 3,
        "train.host_sync": 2}
    steps = sorted(((s, e, st) for n, s, e, st in events if n == "train_step"),
                   key=lambda x: x[0])
    assert [int(st["step_num"]) for *_, st in steps] == [0, 1, 2]
    # every train.* span lies inside one step; the log-cadence syncs are
    # those of the first step and of every ``log_every``-th
    synced = []
    for n, s, e, _ in events:
        if n == "train_step":
            continue
        (k,) = [k for k, (a, b, _) in enumerate(steps) if a <= s and e <= b]
        if n == "train.host_sync":
            synced.append(k)
    assert sorted(synced) == [0, 1]
