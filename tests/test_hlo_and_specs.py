"""HLO collective parsing + tensor-parallel param-spec rules + the
plan/execute byte contract: every compressor's static
``CommSchedule.bytes_per_worker`` must equal both the executed
``SyncStats.bytes_per_worker`` and the collective bytes parsed from the
compiled HLO."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_reduced, list_archs
from repro.core import build_plan, get_compressor
from repro.core.compressors import available
from repro.launch.hlo_analysis import (
    collective_bytes_per_worker,
    collective_summary,
    parse_collectives,
    roofline_terms,
)
from repro.models import build_model, build_param_specs

FAKE_HLO = """
HloModule test
ENTRY main {
  %p0 = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag = (f32[256]{0}, f32[256]{0}) all-gather-start(%p0), dimensions={0}
  %agd = f32[2048]{0} all-gather-done(%ag)
  %a2a = bf16[64,32]{1,0} all-to-all(%p0), dimensions={0}
  %cp = f32[16]{0} collective-permute(%p0), source_target_pairs={{0,1}}
  %rs = f32[128]{0} reduce-scatter(%p0), dimensions={0}, to_apply=%add
}
"""


def test_parse_collectives_counts_and_bytes():
    ops = parse_collectives(FAKE_HLO)
    kinds = sorted(o.kind for o in ops)
    assert kinds == sorted([
        "all-reduce", "all-gather", "all-to-all", "collective-permute",
        "reduce-scatter",
    ])
    by = {o.kind: o.result_bytes for o in ops}
    assert by["all-reduce"] == 4096
    assert by["all-gather"] == 2048  # start tuple counted once, done skipped
    assert by["all-to-all"] == 64 * 32 * 2
    assert by["reduce-scatter"] == 512


def test_collective_summary_wire_factor():
    s = collective_summary(FAKE_HLO)
    raw = s["buffer_bytes"]
    assert s["wire_bytes_est"] == raw + 4096  # all-reduce double-counted


def test_roofline_terms_dominance():
    t = roofline_terms(flops_per_device=197e12, hbm_bytes_per_device=0,
                       wire_bytes_per_device=0)
    assert t.dominant == "compute" and abs(t.compute_s - 1.0) < 1e-9
    t = roofline_terms(flops_per_device=0, hbm_bytes_per_device=819e9,
                       wire_bytes_per_device=100)
    assert t.dominant == "memory"


def test_parse_collectives_fp8_dtypes():
    hlo = """
    HloModule fp8
    ENTRY main {
      %q = f8e4m3fn[8,4096]{1,0} all-gather(%p0), dimensions={0}
      %s = f32[8,1]{1,0} all-gather(%p1), dimensions={0}
    }
    """
    ops = parse_collectives(hlo)
    by = sorted(o.result_bytes for o in ops)
    assert by == [32, 8 * 4096]  # 1 byte/elem fp8 payload + fp32 scales
    assert collective_bytes_per_worker(hlo, 8) == 4096 + 4


# ---- plan/execute byte contract ---------------------------------------------

def _tiny_setup():
    params = {
        "emb": jnp.zeros((128, 16)),
        "w1": jnp.zeros((4, 16, 32)),
        "b1": jnp.zeros((4, 32)),
    }
    plan = build_plan(params, bucket_bytes=2048, max_buckets=16, interval=4)
    key = jax.random.PRNGKey(0)
    grads = {
        k: jax.random.normal(jax.random.fold_in(key, i), v.shape)
        for i, (k, v) in enumerate(params.items())
    }
    return params, plan, grads


@pytest.mark.parametrize("name", available())
def test_schedule_bytes_match_executed_stats(name):
    """For every registered compressor and every phase: plan_phase yields
    a well-formed schedule and execute() reports its bytes.  (SyncStats is
    built *from* the schedule by construction — the independent check that
    planned bytes equal the real collectives is the HLO-parse test below.)
    """
    params, plan, grads = _tiny_setup()
    opts = {"interval": 4} if name == "covap" else {}
    comp = get_compressor(name, **opts)
    state = comp.init_state(params, plan)
    for phase in range(comp.num_phases(4)):
        sched = comp.plan_phase(plan, phase)
        assert sched.phase == phase
        assert sched.bytes_per_worker == sum(
            c.bytes_per_worker for c in sched.calls
        )
        _, _, stats = comp.execute(
            sched, grads, state, step=phase, axis_names=()
        )
        assert stats.bytes_per_worker == sched.bytes_per_worker
        assert stats.dense_bytes == sched.dense_bytes


_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_HLO_MATCH_SUB = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import build_plan, get_compressor
from repro.launch.hlo_analysis import collective_bytes_per_worker

W = 8
mesh = Mesh(np.array(jax.devices()[:W]), ("data",))
params = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((16,))}
plan = build_plan(params, bucket_bytes=512, max_buckets=8, interval=4)
key = jax.random.PRNGKey(0)
gw = {k: jax.random.normal(jax.random.fold_in(key, i), (W,) + v.shape)
      for i, (k, v) in enumerate(params.items())}

CASES = [
    ("none", {}, 0),
    ("fp16", {}, 0),
    ("covap", {"interval": 4}, 0),
    ("covap", {"interval": 4}, 1),
    ("covap", {"interval": 4, "wire_dtype": "bfloat16"}, 0),
    ("topk", {"ratio": 0.05}, 0),
    ("dgc", {"ratio": 0.05}, 0),
    ("randomk", {"ratio": 0.05}, 0),
    ("efsignsgd", {}, 0),
    ("fp8wire", {}, 0),
    ("oktopk", {"ratio": 0.05}, 0),
    ("powersgd", {"rank": 2}, 0),
]
for name, opts, phase in CASES:
    comp = get_compressor(name, **opts)
    state = comp.init_state(params, plan)
    sched = comp.plan_phase(plan, phase, world=W)

    def run(g, s):
        g = {k: v[0] for k, v in g.items()}
        out, s2, _ = comp.execute(sched, g, s, step=0, axis_names=("data",))
        return out, s2

    f = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P("data"), P()), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))
    hlo = f.lower(gw, state).compile().as_text()
    got = collective_bytes_per_worker(hlo, W)
    # The CPU backend widens narrow wire formats inside collectives
    # (AllReducePromotion: bf16 all-reduce -> f32; fp8 all-gathers go out
    # as f16), so a planned narrow wire physically moves 2x the bytes on
    # CPU — noted in repro.core.comm._promote_bf16.  On TPU the planned
    # wire dtype goes out as-is and expected == planned exactly.
    def expected_bytes(c):
        if c.wire_dtype == "bfloat16" and c.op == "all_reduce":
            return c.payload_bytes * 2 + c.index_bytes
        if c.wire_dtype.startswith("float8") and c.op == "all_gather":
            return c.payload_bytes * 2 + c.index_bytes
        return c.bytes_per_worker

    expected = sum(expected_bytes(c) for c in sched.calls)
    assert int(got) == expected, (name, phase, int(got), expected)
    print(name, phase, "OK", int(got))
"""


def test_schedule_bytes_match_hlo_collectives():
    """The planned bytes ARE the compiled collectives: for every compressor,
    ``CommSchedule.bytes_per_worker`` equals the per-worker collective bytes
    parsed from the optimized HLO of ``execute`` under an 8-way shard_map."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_HLO_MATCH_SUB)],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert r.stdout.count("OK") == 12


_SHARDED_HLO_SUB = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import build_plan, get_compressor
from repro.core.overlap import sharded_param_allgather
from repro.launch.hlo_analysis import collective_bytes_per_worker, parse_collectives

W = 8
mesh = Mesh(np.array(jax.devices()[:W]), ("data",))
params = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((16,))}
plan = build_plan(params, bucket_bytes=512, max_buckets=8, interval=4)
key = jax.random.PRNGKey(0)
gw = {k: jax.random.normal(jax.random.fold_in(key, i), (W,) + v.shape)
      for i, (k, v) in enumerate(params.items())}

CASES = [
    ("none", {}, 0),
    ("fp16", {}, 0),
    ("covap", {"interval": 4}, 0),
    ("covap", {"interval": 4}, 1),
]
for name, opts, phase in CASES:
    comp = get_compressor(name, **opts, sync="sharded")
    state = comp.init_state(params, plan)
    sched = comp.plan_phase(plan, phase, world=W)

    # ---- the RS half: execute()'s compiled collectives ------------------
    def run(g, s):
        g = {k: v[0] for k, v in g.items()}
        out, s2, _ = comp.execute(sched, g, s, step=0, axis_names=("data",))
        return out, s2

    f = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(P("data"), P()), out_specs=(P(), P()),
        axis_names={"data"}, check_vma=False))
    hlo = f.lower(gw, state).compile().as_text()
    got = collective_bytes_per_worker(hlo, W)
    kinds = {o.kind for o in parse_collectives(hlo)}
    assert kinds <= {"reduce-scatter"}, kinds
    # CPU backend promotes narrow reduction operands (the same
    # AllReducePromotion note as the all-reduce cases): a planned bf16
    # reduce-scatter physically moves f32 on the dry-run backend
    def expected_bytes(c):
        if c.wire_dtype == "bfloat16" and c.op == "reduce_scatter":
            return c.payload_bytes * 2 + c.index_bytes
        return c.bytes_per_worker

    expected = sum(expected_bytes(c) for c in sched.calls)
    assert int(got) == expected, (name, phase, int(got), expected)

    # ---- the AG half: the head/flush program's compiled collectives -----
    def head(p):
        return sharded_param_allgather(comp, sched, p, axis_names=("data",))

    fh = jax.jit(jax.shard_map(head, mesh=mesh, in_specs=(P(),), out_specs=P(),
                               axis_names={"data"}, check_vma=False))
    hlo_h = fh.lower(params).compile().as_text()
    got_h = collective_bytes_per_worker(hlo_h, W)
    kinds_h = {o.kind for o in parse_collectives(hlo_h)}
    assert kinds_h <= {"all-gather"}, kinds_h
    expected_h = sum(c.bytes_per_worker for c in sched.deferred_calls)
    assert int(got_h) == expected_h, (name, phase, int(got_h), expected_h)
    print(name, phase, "SHARDED-OK", int(got), int(got_h))
"""


def test_sharded_schedule_bytes_match_hlo_collectives():
    """Sharded sync's two halves cross-checked against compiled HLO: the
    RS bytes of ``execute`` equal ``schedule.bytes_per_worker`` and the AG
    bytes of the head/flush program equal
    ``schedule.deferred_bytes_per_worker`` — per-worker-normalised by the
    reduce-scatter/all-gather rules of ``collective_bytes_per_worker``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SHARDED_HLO_SUB)],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert r.stdout.count("SHARDED-OK") == 4


# ---- param specs -------------------------------------------------------------

@pytest.mark.parametrize("arch", list_archs())
def test_param_specs_cover_all_leaves(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = build_param_specs(cfg, model.init, 2, "model")
    s_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    p_leaves = jax.tree.leaves(shapes)
    assert len(s_leaves) == len(p_leaves)
    for spec, leaf in zip(s_leaves, p_leaves):
        assert isinstance(spec, P)
        # divisibility respected
        for ax, name in enumerate(spec):
            if name is not None and ax < len(leaf.shape):
                assert leaf.shape[ax] % 2 == 0


def test_param_specs_shard_big_matrices_full_config():
    cfg = get_config("mistral-large-123b")
    model = build_model(cfg)
    specs = build_param_specs(cfg, model.init, 16, "model")
    flat = {
        "/".join(str(getattr(k, "key", k)) for k in path): s
        for path, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }
    sharded = [k for k, s in flat.items() if any(a is not None for a in s)]
    assert any("wq" in k for k in sharded)
    assert any("w_down" in k for k in sharded)
    assert any("head" in k for k in sharded)


def test_moe_expert_parallel_spec():
    cfg = get_config("deepseek-moe-16b")  # 64 experts % 16 == 0
    model = build_model(cfg)
    specs = build_param_specs(cfg, model.init, 16, "model")
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P)
    )[0]
    moe_specs = [
        s for p, s in flat
        if "moe" in (jp := "/".join(str(getattr(k, "key", k)) for k in p))
        and "w_gate" in jp and "shared" not in jp
    ]
    assert moe_specs, "expected MoE expert leaves"
    for s in moe_specs:
        # stacked (n_super, E, d, ff): expert axis sharded
        assert s[-3] == "model"
