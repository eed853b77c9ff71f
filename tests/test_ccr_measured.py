"""The measured CCR profiler (paper §III.B): ``measure_ccr`` sub-program
timing and ``align_comm_times`` distributed-timeline alignment — including
on a real (fake-device) CPU mesh, where the full step carries genuine
shard_map collectives."""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro.core.ccr import align_comm_times, measure_ccr, select_interval

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# align_comm_times: pure arithmetic
# ---------------------------------------------------------------------------

def test_align_excludes_rendezvous_wait():
    # worker 0 reaches the collective early and waits; the true transfer
    # only starts when worker 1 (the straggler) arrives
    starts = np.array([[0.0], [3.0]])
    ends = np.array([[5.0], [5.0]])
    assert align_comm_times(starts, ends) == pytest.approx([2.0])


def test_align_multiple_ops_uses_last_start_first_end():
    starts = np.array([[0.0, 10.0], [1.0, 12.0], [0.5, 11.0]])
    ends = np.array([[4.0, 15.0], [4.5, 14.0], [4.0, 15.5]])
    got = align_comm_times(starts, ends)
    assert got == pytest.approx([4.0 - 1.0, 14.0 - 12.0])


def test_align_single_worker_is_plain_duration():
    starts = np.array([[1.0, 2.0]])
    ends = np.array([[1.5, 4.0]])
    assert align_comm_times(starts, ends) == pytest.approx([0.5, 2.0])


# ---------------------------------------------------------------------------
# measure_ccr: sub-program timing
# ---------------------------------------------------------------------------

def test_measure_ccr_with_synthetic_sleeps():
    full = lambda: time.sleep(0.012)
    comp = lambda: time.sleep(0.004)
    res = measure_ccr(full, comp, warmup=1, iters=3)
    assert res["t_full"] > res["t_comp"] > 0
    # t_comm ~ 8ms, t_comp ~ 4ms -> CCR ~ 2 (generous CI tolerance)
    assert 0.8 < res["ccr"] < 5.0
    assert select_interval(res["ccr"]) >= 1


def test_measure_ccr_comm_only_crosscheck_takes_max():
    # overlap makes (t_full - t_comp) undershoot; the direct schedule-only
    # timing must win when it is larger
    full = lambda: time.sleep(0.004)
    comp = lambda: time.sleep(0.004)
    comm = lambda: time.sleep(0.008)
    res = measure_ccr(full, comp, step_comm_only=comm, warmup=0, iters=2)
    assert "t_comm_direct" in res
    assert res["t_comm"] >= res["t_comm_direct"] * 0.8
    assert res["ccr"] > 1.0


def test_measure_ccr_comm_free_workload():
    fn = lambda: sum(range(2000))
    res = measure_ccr(fn, fn, warmup=1, iters=3)
    assert res["t_comm"] < res["t_comp"] + 1e-3
    # tiny jitter only: the derived interval should stay minimal
    assert select_interval(res["ccr"]) <= 2


# ---------------------------------------------------------------------------
# on a CPU mesh (8 fake devices, subprocess so the device count cannot
# leak into other tests)
# ---------------------------------------------------------------------------

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


def test_measure_ccr_on_cpu_mesh():
    """Full step = compute + psum over a 'data' mesh; compute-only elides
    the collective.  The profiler must produce a finite decomposition with
    t_full >= t_comp (within timing noise)."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.ccr import measure_ccr

mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
x = jnp.arange(8 * 4096, dtype=jnp.float32).reshape(8, 4096)

def full_worker(x):
    y = jnp.tanh(x) @ jnp.ones((x.shape[-1], 64))
    return jax.lax.psum(y, "data")

def comp_worker(x):
    return jnp.tanh(x) @ jnp.ones((x.shape[-1], 64))

full = jax.jit(jax.shard_map(full_worker, mesh=mesh, in_specs=(P("data"),), out_specs=P(), axis_names={"data"}, check_vma=False))
comp = jax.jit(jax.shard_map(comp_worker, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"), axis_names={"data"}, check_vma=False))

res = measure_ccr(
    lambda: jax.block_until_ready(full(x)),
    lambda: jax.block_until_ready(comp(x)),
    warmup=2, iters=5,
)
assert res["t_full"] > 0 and res["t_comp"] > 0
assert np.isfinite(res["ccr"]) and res["ccr"] >= 0.0
print("ccr=%.4f" % res["ccr"])
""")
    assert "ccr=" in out


def test_schedule_only_program_on_cpu_mesh():
    """runtime's schedule-only sub-program: replays exactly the planned
    collectives of a COVAP phase on a mesh and is timeable."""
    out = run_sub("""
import time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import build_plan, get_compressor
from repro.runtime import build_schedule_only_fn

mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
params = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((16,))}
plan = build_plan(params, bucket_bytes=512, max_buckets=8, interval=4)
comp = get_compressor("covap", interval=4)
sched = comp.plan_phase(plan, 0, world=8)
fn = build_schedule_only_fn(sched, mesh=mesh, dp_axes=("data",))
fn()  # compile
t0 = time.perf_counter(); fn(); dt = time.perf_counter() - t0
assert dt >= 0.0
print("sched_only_ok %d calls" % len(sched.calls))
""")
    assert "sched_only_ok" in out
