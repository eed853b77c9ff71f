"""Multi-worker correctness: compressors + trainer under shard_map on 8
fake CPU devices.  Runs in a subprocess because the device count must be
set before jax initialises (and must NOT leak into other tests)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import build_plan, get_compressor

mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
params = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((16,))}
plan = build_plan(params, bucket_bytes=512, max_buckets=8, interval=4)
key = jax.random.PRNGKey(0)
# per-worker distinct gradients: (8, ...) leading axis
gw = {k: jax.random.normal(jax.random.fold_in(key, i), (8,) + v.shape)
      for i, (k, v) in enumerate(params.items())}
"""


def test_compressor_psum_equals_mean():
    """For mean-exact schemes the multi-worker sync must equal the mean of
    per-worker gradients at communicated positions."""
    out = run_sub(PRELUDE + """
for name in ("none", "covap", "fp16", "randomk"):
    comp = get_compressor(name, **({"interval": 4} if name == "covap" else {}))
    state = comp.init_state(params, plan)

    # shard_map splits leading axis 8 -> per-worker (1, ...) ... need squeeze
    def sync_worker(g, s):
        g = {k: v[0] for k, v in g.items()}
        out, s2, _ = comp.sync(g, s, plan=plan, phase=0, step=0,
                               axis_names=("data",))
        return out
    f = jax.jit(jax.shard_map(sync_worker, mesh=mesh,
        in_specs=(P("data"), P()), out_specs=P(), axis_names={"data"},
        check_vma=False))
    got = f(gw, state)
    mean = {k: v.mean(axis=0) for k, v in gw.items()}
    # compare only where the scheme communicated (out != 0)
    for k in mean:
        g_np, m_np = np.asarray(got[k]), np.asarray(mean[k])
        mask = g_np != 0
        if name in ("none", "fp16"):
            mask = np.ones_like(g_np, bool)
        tol = 2e-2 if name == "fp16" else 1e-5
        np.testing.assert_allclose(g_np[mask], m_np[mask], rtol=tol, atol=tol)
    print(name, "OK")
""")
    assert out.count("OK") == 4


def test_allgather_schemes_run_multiworker():
    out = run_sub(PRELUDE + """
for name in ("topk", "efsignsgd", "oktopk", "fp8wire"):
    comp = get_compressor(name)
    state = comp.init_state(params, plan)
    def sync_worker(g, s):
        g = {k: v[0] for k, v in g.items()}
        out, s2, _ = comp.sync(g, s, plan=plan, phase=0, step=0,
                               axis_names=("data",))
        return out
    f = jax.jit(jax.shard_map(sync_worker, mesh=mesh,
        in_specs=(P("data"), P()), out_specs=P(), axis_names={"data"},
        check_vma=False))
    got = f(gw, state)
    for k in got:
        assert bool(jnp.all(jnp.isfinite(got[k]))), name
    print(name, "OK")
""")
    assert out.count("OK") == 4


def test_trainer_covap_multiworker_loss_decreases():
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import get_reduced
from repro.models import build_model
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer

mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
cfg = get_reduced("gpt2-paper")
model = build_model(cfg)
tc = TrainConfig(compressor="covap", interval=2, bucket_bytes=1 << 14,
                 max_buckets=32, log_every=100)
tr = Trainer(model, adamw(3e-3), tc, mesh=mesh, dp_axes=("data",))
state = tr.init_state(jax.random.PRNGKey(0))

from repro.data import DataConfig, make_loader
dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                corpus_tokens=1 << 14)
loader = iter(make_loader(dc))
first = None
losses = []
for i in range(12):
    batch = next(loader)
    phase = state["step"] % tr.num_phases
    fn = tr._phase_fn(phase)
    p, o, c, m = fn(state["params"], state["opt"], state["comp"], batch,
                    jnp.int32(state["step"]))
    state = {"params": p, "opt": o, "comp": c, "step": state["step"] + 1}
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("loss", losses[0], "->", losses[-1], "OK")
""")
    assert "OK" in out
