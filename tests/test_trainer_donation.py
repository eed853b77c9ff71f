"""The phase executables donate the train state: ``Trainer.run`` consumes
the params, optimizer moments and EF residuals it is handed, every byte
of them aliases an output, and the steps equal, bit for bit, the same
steps built without donation.

The four-device cases run in a subprocess (the fake CPU device count must
be set before jax initialises), through the same ``check_donation``."""
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 3


def _trainer(overlap: str, sync: str, devices: int):
    from jax.sharding import Mesh

    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.optim import adamw
    from repro.train.trainer import TrainConfig, Trainer

    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    tc = TrainConfig(compressor="covap", interval=2, bucket_bytes=1 << 14,
                     max_buckets=16, log_every=1000, overlap=overlap,
                     sync=sync)
    if devices == 1:
        return Trainer(build_model(cfg), adamw(3e-3), tc)
    mesh = Mesh(np.array(jax.devices()[:devices]), ("data",))
    return Trainer(build_model(cfg), adamw(3e-3), tc, mesh=mesh,
                   dp_axes=("data",))


def _batches():
    from repro.data import DataConfig, make_loader

    dc = DataConfig(vocab_size=256, seq_len=16, global_batch=8,
                    corpus_tokens=1 << 12)
    return [b for b, _ in zip(make_loader(dc), range(STEPS))]


def check_donation(overlap: str, sync: str, devices: int) -> None:
    from repro.obs import Telemetry
    from repro.train import trainer as trainer_mod

    batches = _batches()
    tr = _trainer(overlap, sync, devices)
    state = tr.init_state(jax.random.PRNGKey(0))
    handed = [state[k] for k in ("params", "opt", "comp")]
    tel = Telemetry()
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message=".*donated buffers were not usable")
        out = tr.run(state, iter(batches), steps=STEPS, log=None,
                     telemetry=tel)
    jax.block_until_ready(out["params"])
    assert all(x.is_deleted() for x in jax.tree.leaves(handed))
    shares = {k: v for k, v in tel.registry.snapshot().items()
              if k.startswith("train_state_donated_share")}
    assert len(shares) == tr.num_phases, shares
    assert all(v == 1.0 for v in shares.values()), shares

    # the same steps through executables built without donation
    build = trainer_mod.build_train_step
    trainer_mod.build_train_step = (
        lambda *a, **kw: build(*a, **dict(kw, donate=False)))
    try:
        ref_tr = _trainer(overlap, sync, devices)
        start = ref_tr.init_state(jax.random.PRNGKey(0))
        ref = ref_tr.run(start, iter(batches), steps=STEPS, log=None)
    finally:
        trainer_mod.build_train_step = build
    kept = [start[k] for k in ("params", "opt", "comp")]
    assert not any(x.is_deleted() for x in jax.tree.leaves(kept))
    assert out["step"] == ref["step"] == STEPS
    for k in ("params", "opt", "comp"):
        for a, b in zip(jax.tree.leaves(out[k]), jax.tree.leaves(ref[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


CASES = [
    ("post", "allreduce", 1),
    ("fused", "allreduce", 1),
    ("post", "allreduce", 4),
    ("fused", "allreduce", 4),
    ("post", "sharded", 4),
]


@pytest.mark.parametrize("overlap,sync,devices", CASES)
def test_run_donates_the_whole_state_and_matches_undonated_steps(
    overlap, sync, devices,
):
    if devices == 1:
        check_donation(overlap, sync, devices)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, HERE, env.get("PYTHONPATH", "")])
    code = (f"import test_trainer_donation as t; "
            f"t.check_donation({overlap!r}, {sync!r}, {devices})")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
