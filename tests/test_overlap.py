"""Overlap execution engine: ReadyOrder properties, fused==post bit-for-bit
equivalence (single-process and 8-worker CPU mesh), the HLO interleaving
checker, and the fused EF kernel's wiring into the segmented execute path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import build_plan, build_ready_order, get_compressor
from repro.core import perfmodel as pm
from repro.core.overlap import (
    overlapped_loss_and_grads,
    supports_fused_overlap,
)
from repro.data import DataConfig, make_loader
from repro.launch.hlo_analysis import check_interleaving
from repro.models import build_model
from repro.optim import adamw
from repro.train.trainer import (
    TrainConfig,
    Trainer,
    strip_pod_block,
)

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# ReadyOrder: reverse-topological readiness properties
# ---------------------------------------------------------------------------

def _arch_plan(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return build_plan(shapes, bucket_bytes=1 << 13, max_buckets=64, interval=4)


@pytest.mark.parametrize(
    "arch", ["gpt2-paper", "deepseek-moe-16b", "seamless-m4t-medium"]
)
def test_ready_order_is_reverse_layer_permutation(arch):
    """For transformer, MoE and enc-dec stacks: ReadyOrder is a permutation
    of the buckets, monotone in reverse layer order (deeper layer -> lower
    rank), with head buckets first and embedding buckets last."""
    plan = _arch_plan(arch)
    ready = build_ready_order(plan)
    nb = plan.num_buckets

    # a permutation of the buckets
    assert sorted(ready.ranks) == list(range(nb))
    assert sorted(ready.order) == list(range(nb))
    assert len(ready.bucket_layer) == nb

    # strictly consistent with reverse layer order: a bucket whose last
    # gradient comes from a deeper layer is issued strictly earlier
    for a in range(nb):
        for b in range(nb):
            if ready.bucket_layer[a] > ready.bucket_layer[b]:
                assert ready.ranks[a] < ready.ranks[b]

    def buckets_only_in(marker):
        # buckets ALL of whose segments belong to `marker` leaves (a DDP
        # packer may straddle the embed/head boundary in one bucket; such
        # a bucket is ready only with its shallowest member)
        out = set()
        for bi, bucket in enumerate(plan.buckets):
            if all(
                marker in plan.leaf_paths[seg.leaf_idx]
                for seg in bucket.segments
            ):
                out.add(bi)
        return out

    head = buckets_only_in("head")
    embed = buckets_only_in("embed")
    assert head and embed
    # the head's VJP runs first in the backward pass; the embedding's last
    assert max(ready.ranks[b] for b in head) < min(
        ready.ranks[b] for b in embed
    )


def test_ready_order_stacked_rows_reverse():
    """Within a scan-stacked leaf, higher rows (later layers) are ready
    earlier."""
    plan = _arch_plan("gpt2-paper")
    ready = build_ready_order(plan)
    # collect (row, rank) for single-leaf block buckets
    rows = {}
    for bi, bucket in enumerate(plan.buckets):
        segs = bucket.segments
        if any("blocks" not in plan.leaf_paths[s.leaf_idx] for s in segs):
            continue
        rows.setdefault(min(s.row_lo for s in segs), []).append(
            ready.ranks[bi]
        )
    keys = sorted(rows)
    assert len(keys) >= 2
    for lo, hi in zip(keys, keys[1:]):
        # every bucket of row `hi` issues before every bucket of row `lo`
        assert max(rows[hi]) < min(rows[lo])


def test_ready_order_toy_tree_is_reverse_param_order():
    params = {"a": jnp.zeros((8, 4)), "b": jnp.zeros((8, 4)),
              "c": jnp.zeros((4,))}
    plan = build_plan(params, bucket_bytes=64, max_buckets=16, interval=2)
    ready = build_ready_order(plan)
    assert sorted(ready.ranks) == list(range(plan.num_buckets))
    # unknown paths: one depth slot per leaf, so readiness is reverse
    # parameter order — the last leaf's bucket issues first
    first = ready.order[0]
    last = ready.order[-1]
    assert plan.buckets[first].segments[0].leaf_idx >= \
        plan.buckets[last].segments[0].leaf_idx


def test_schedule_carries_ready_ranks():
    params = {"w": jnp.zeros((64, 16)), "b": jnp.zeros((16,))}
    plan = build_plan(params, bucket_bytes=512, max_buckets=8, interval=4)
    comp = get_compressor("covap", interval=4)
    sched = comp.plan_phase(plan, 0)
    assert len(sched.ready_ranks) == len(sched.calls)
    order = sched.issue_order()
    ranks = [sched.ready_ranks[i] for i in order]
    assert ranks == sorted(ranks)
    # dense plan: every bucket, ranks are exactly the ReadyOrder ranks
    dense = get_compressor("none").plan_phase(plan, 0)
    ready = build_ready_order(plan)
    assert dense.ready_ranks == tuple(
        ready.rank_of(b) for b in dense.selected
    )


# ---------------------------------------------------------------------------
# fused == post (single process)
# ---------------------------------------------------------------------------

def _train(compressor, overlap, steps, **copts):
    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    model = build_model(cfg)
    tc = TrainConfig(
        compressor=compressor, compressor_options=copts, interval=4,
        bucket_bytes=1 << 14, max_buckets=32, log_every=10 ** 9,
        overlap=overlap,
    )
    tr = Trainer(model, adamw(3e-3), tc)
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                    corpus_tokens=1 << 14)
    loader = iter(make_loader(dc))
    for _ in range(steps):
        batch = next(loader)
        fn = tr._phase_fn(state["step"] % tr.num_phases)
        p, o, c, m = fn(state["params"], state["opt"], state["comp"], batch,
                        jnp.int32(state["step"]))
        state = {"params": p, "opt": o, "comp": c, "step": state["step"] + 1}
    return state


def _assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("compressor", ["covap", "none", "fp16"])
def test_fused_equals_post_single_process(compressor):
    """A full phase cycle + one: params AND EF residuals bit-for-bit."""
    steps = 5  # full covap cycle (4 phases) + 1
    post = _train(compressor, "post", steps)
    fused = _train(compressor, "fused", steps)
    _assert_tree_equal(post["params"], fused["params"])
    _assert_tree_equal(post["comp"], fused["comp"])


def test_fused_rejects_flat_and_leaf_pipelines():
    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    model = build_model(cfg)
    for name in ("topk", "powersgd"):
        comp = get_compressor(name)
        assert not supports_fused_overlap(comp)
        tc = TrainConfig(compressor=name, interval=4, bucket_bytes=1 << 14,
                         max_buckets=16, overlap="fused")
        tr = Trainer(model, adamw(1e-3), tc)
        with pytest.raises(ValueError, match="overlap"):
            tr._phase_fn(0)


# ---------------------------------------------------------------------------
# fused == post on an 8-worker CPU mesh (the acceptance criterion) + the
# compiled-HLO interleaving check.  Subprocess: the fake device count must
# be set before jax initialises.
# ---------------------------------------------------------------------------

_MESH_SUB = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import get_reduced
from repro.data import DataConfig, make_loader
from repro.launch.hlo_analysis import check_interleaving
from repro.models import build_model
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer

mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
model = build_model(cfg)

def run(overlap, compressor, steps=5):
    tc = TrainConfig(compressor=compressor, interval=4, bucket_bytes=1 << 14,
                     max_buckets=32, log_every=10 ** 9, overlap=overlap)
    tr = Trainer(model, adamw(3e-3), tc, mesh=mesh, dp_axes=("data",))
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                    corpus_tokens=1 << 14)
    loader = iter(make_loader(dc))
    for _ in range(steps):
        batch = next(loader)
        fn = tr._phase_fn(state["step"] % tr.num_phases)
        p, o, c, m = fn(state["params"], state["opt"], state["comp"], batch,
                        jnp.int32(state["step"]))
        state = {"params": p, "opt": o, "comp": c,
                 "step": state["step"] + 1}
    return tr, state, batch

for compressor in ("covap", "none"):
    tr_p, post, batch = run("post", compressor)
    tr_f, fused, _ = run("fused", compressor)
    for x, y in zip(jax.tree.leaves(post["params"]),
                    jax.tree.leaves(fused["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(post["comp"]),
                    jax.tree.leaves(fused["comp"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    print(compressor, "EQUAL")

    # interleaving: the fused module schedules at least one bucket
    # collective before the final gradient-producing fusion (shared
    # harness with the benchmarks.run --smoke "overlap" gate)
    from repro.launch.overlap_gate import compile_and_check
    r = compile_and_check(tr_f, fused, batch)
    assert r.num_collectives > 0, r
    assert r.interleaved, r
    print(compressor, "INTERLEAVED", r.before_final_grad)

# hierarchical pods: fused == post numerically (XLA fusion choices may
# differ at the ulp level between the two programs; bitwise pinning is a
# pure-DP-mesh property)
hmesh = jax.make_mesh((2, 4), ("pod", "data"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)

def run_hier(overlap, steps=4):
    tc = TrainConfig(compressor="covap", interval=2, pod_interval=2,
                     bucket_bytes=1 << 14, max_buckets=16,
                     log_every=10 ** 9, overlap=overlap)
    tr = Trainer(model, adamw(3e-3), tc, mesh=hmesh,
                 dp_axes=("pod", "data"))
    state = tr.init_state(jax.random.PRNGKey(0))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8,
                    corpus_tokens=1 << 13)
    loader = iter(make_loader(dc))
    for _ in range(steps):
        b = next(loader)
        fn = tr._phase_fn(state["step"] % tr.num_phases)
        p, o, c, m = fn(state["params"], state["opt"], state["comp"], b,
                        jnp.int32(state["step"]))
        state = {"params": p, "opt": o, "comp": c,
                 "step": state["step"] + 1}
    return state

hp, hf = run_hier("post"), run_hier("fused")
for x, y in zip(jax.tree.leaves(hp["params"]), jax.tree.leaves(hf["params"])):
    np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                               rtol=1e-5, atol=1e-6)
for x, y in zip(jax.tree.leaves(hp["comp"]), jax.tree.leaves(hf["comp"])):
    np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                               rtol=1e-5, atol=1e-6)
print("HIER_CLOSE")
"""


def test_fused_equals_post_on_cpu_mesh():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESH_SUB)],
        capture_output=True, text=True, timeout=560, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert r.stdout.count("EQUAL") == 2
    assert r.stdout.count("INTERLEAVED") == 2
    assert "HIER_CLOSE" in r.stdout


# ---------------------------------------------------------------------------
# interleaving checker unit tests (synthetic HLO)
# ---------------------------------------------------------------------------

_HLO_INTERLEAVED = """
HloModule m
ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %g1 = f32[1024]{0} fusion(f32[1024]{0} %p0), kind=kLoop, calls=%fc.1
  %ar1 = f32[1024]{0} all-reduce(f32[1024]{0} %g1), to_apply=%add
  %g2 = f32[1024]{0} fusion(f32[1024]{0} %p0), kind=kLoop, calls=%fc.2
  %ar2 = f32[1024]{0} all-reduce(f32[1024]{0} %g2), to_apply=%add
  %out = f32[1024]{0} add(f32[1024]{0} %ar1, f32[1024]{0} %ar2)
}
"""

_HLO_SERIAL = """
HloModule m
ENTRY %main (p0: f32[1024]) -> f32[1024] {
  %p0 = f32[1024]{0} parameter(0)
  %g1 = f32[1024]{0} fusion(f32[1024]{0} %p0), kind=kLoop, calls=%fc.1
  %g2 = f32[1024]{0} fusion(f32[1024]{0} %g1), kind=kLoop, calls=%fc.2
  %ar1 = f32[1024]{0} all-reduce(f32[1024]{0} %g1), to_apply=%add
  %ar2 = f32[1024]{0} all-reduce(f32[1024]{0} %g2), to_apply=%add
  %out = f32[1024]{0} add(f32[1024]{0} %ar1, f32[1024]{0} %ar2)
}
"""


def test_check_interleaving_synthetic():
    r = check_interleaving(_HLO_INTERLEAVED)
    assert r.num_collectives == 2
    # ar1 is scheduled before g2 (the final grad-producing fusion) and is
    # structurally independent of it
    assert r.interleaved and r.before_final_grad == 1
    assert r.independent >= 1

    r = check_interleaving(_HLO_SERIAL)
    assert r.num_collectives == 2
    assert not r.interleaved and r.before_final_grad == 0


def test_check_interleaving_ignores_scalar_psums():
    hlo = """
HloModule m
ENTRY %main (p0: f32[]) -> f32[] {
  %p0 = f32[] parameter(0)
  %loss = f32[] all-reduce(f32[] %p0), to_apply=%add
  %g = f32[] fusion(f32[] %loss), kind=kLoop, calls=%fc
}
"""
    r = check_interleaving(hlo)
    assert r.num_collectives == 0 and not r.interleaved


# ---------------------------------------------------------------------------
# overlap fraction accounting (predicted vs achieved)
# ---------------------------------------------------------------------------

def test_overlap_fraction_bounds():
    # fully hidden: comm fits entirely under remaining compute
    sim = pm.simulate_overlap(0.1, [0.2] * 4, [0.01] * 4)
    assert pm.overlap_fraction(sim) > 0.7
    # fully exposed: all comm after the last bucket's compute
    sim = pm.simulate_overlap(0.0, [0.0] * 4, [0.1] * 4)
    assert pm.overlap_fraction(sim) == 0.0
    assert pm.overlap_fraction({"comm_total": 0.0}) == 1.0

    assert pm.achieved_overlap_fraction(1.0, 0.5, 1.0) == 1.0
    assert pm.achieved_overlap_fraction(1.0, 0.5, 1.5) == 0.0
    assert abs(pm.achieved_overlap_fraction(1.0, 0.5, 1.25) - 0.5) < 1e-9
    assert pm.achieved_overlap_fraction(1.0, 0.0, 2.0) == 1.0


def test_simulate_schedule_ready_order():
    # unequal leaf sizes -> unequal per-bucket comm times, so a regression
    # that permutes comp but not comm (or neither) changes the timeline
    params = {"embed": {"table": jnp.zeros((64, 16))},
              "head": {"w": jnp.zeros((16, 100))}}
    plan = build_plan(params, bucket_bytes=1024, max_buckets=16, interval=2)
    sched = get_compressor("none").plan_phase(plan, 0, world=8)
    a = pm.simulate_schedule(0.1, 1.0, sched, world=8, link_bw=1e6)
    b = pm.simulate_schedule(0.1, 1.0, sched, world=8, link_bw=1e6,
                             ready_order=True)
    # same work either way, just a different timeline layout
    assert abs(a["comm_total"] - b["comm_total"]) < 1e-12
    # the ready_order branch must lay the timeline out exactly as
    # simulate_overlap over the (comp, comm) lists permuted by ReadyOrder
    order = build_ready_order(plan).order
    numels = plan.bucket_numels()
    total = sum(numels)
    comp = [1.0 * n / total for n in numels]
    comm = pm.schedule_comm_times(sched, world=8, link_bw=1e6)
    expect = pm.simulate_overlap(
        0.1, [comp[i] for i in order], [comm[i] for i in order]
    )
    assert b == expect
    # and the permutation is non-trivial for this embed+head tree (head
    # buckets issue first)
    assert tuple(order) != tuple(range(len(order)))
    assert [comm[i] for i in order] != comm


def test_monitor_reports_achieved_overlap():
    from repro.runtime.monitor import CCRMonitor, PhaseSample

    mon = CCRMonitor()
    mon.record_sample(PhaseSample(phase=0, t_comp=1.0, t_comm=0.5,
                                  t_full=1.25))
    mt = mon.measured_times()
    assert abs(mt["achieved_overlap"] - 0.5) < 1e-9
    assert abs(mon.summary()["achieved_overlap"] - 0.5) < 1e-9
    # synthetic samples (no wall time) stay None
    mon2 = CCRMonitor()
    mon2.record_sample(PhaseSample(phase=0, t_comp=1.0, t_comm=0.5))
    assert "achieved_overlap" not in (mon2.measured_times() or {})
    assert mon2.summary()["achieved_overlap"] is None


# ---------------------------------------------------------------------------
# fused EF kernel wiring (satellite): segmented COVAP path
# ---------------------------------------------------------------------------

def _covap_setup(use_kernel, **opts):
    params = {"w": jnp.zeros((64, 16), jnp.float32),
              "b": jnp.zeros((16,), jnp.float32)}
    plan = build_plan(params, bucket_bytes=512, max_buckets=8, interval=4)
    comp = get_compressor("covap", interval=4, use_ef_kernel=use_kernel,
                          **opts)
    return params, plan, comp


def test_covap_ef_kernel_exact_parity_on_exact_inputs():
    """Bit-for-bit parity of the kernel-wired segmented path against the
    jnp reference across selected/unselected phases, on inputs whose
    products are exact (residuals = powers of two, coefficient 0.5): this
    isolates wiring bugs from the kernel's FMA rounding, which is the only
    permitted difference (see kernels/ef_covap.py)."""
    exact = dict(ef_init=0.5, ef_ascend_steps=10 ** 9, ef_ascend_range=0.0)
    params, plan, comp_k = _covap_setup(True, **exact)
    _, _, comp_r = _covap_setup(False, **exact)
    key = jax.random.PRNGKey(0)
    grads = {
        k: jax.random.normal(jax.random.fold_in(key, i), v.shape)
        for i, (k, v) in enumerate(params.items())
    }
    # exact products: r in {2^k}, coefficient pinned at 0.5 — c*r is exact,
    # so FMA (one rounding) == mul+add (two roundings) bit-for-bit
    resid = {
        k: jnp.exp2(
            jax.random.randint(jax.random.fold_in(key, 7 + i), v.shape, -3, 3)
            .astype(jnp.float32)
        )
        for i, (k, v) in enumerate(params.items())
    }
    state_k, state_r = dict(resid), dict(resid)
    for step in range(8):  # two full cycles: every bucket selected twice
        phase = step % 4
        sk = comp_k.plan_phase(plan, phase)
        sr = comp_r.plan_phase(plan, phase)
        out_k, state_k, _ = comp_k.execute(sk, grads, state_k, step=step)
        out_r, state_r, _ = comp_r.execute(sr, grads, state_r, step=step)
        for k in grads:
            np.testing.assert_array_equal(np.asarray(out_k[k]),
                                          np.asarray(out_r[k]))
            np.testing.assert_array_equal(np.asarray(state_k[k]),
                                          np.asarray(state_r[k]))


def test_covap_ef_kernel_close_on_random_inputs():
    """On arbitrary inputs the kernel may differ from the 2-op reference by
    FMA rounding only (~1 ulp)."""
    params, plan, comp_k = _covap_setup(True)
    _, _, comp_r = _covap_setup(False)
    key = jax.random.PRNGKey(1)
    grads = {
        k: jax.random.normal(jax.random.fold_in(key, i), v.shape)
        for i, (k, v) in enumerate(params.items())
    }
    state_k = comp_k.init_state(params, plan)
    state_r = comp_r.init_state(params, plan)
    state_k = jax.tree.map(lambda a: a + 0.3, state_k)
    state_r = jax.tree.map(lambda a: a + 0.3, state_r)
    for step in range(4):
        sk = comp_k.plan_phase(plan, step % 4)
        out_k, state_k, _ = comp_k.execute(sk, grads, state_k, step=step)
        out_r, state_r, _ = comp_r.execute(sk, grads, state_r, step=step)
        for k in grads:
            np.testing.assert_allclose(
                np.asarray(out_k[k]), np.asarray(out_r[k]),
                rtol=1e-6, atol=1e-6,
            )
            np.testing.assert_allclose(
                np.asarray(state_k[k]), np.asarray(state_r[k]),
                rtol=1e-6, atol=1e-6,
            )


def test_fused_overlap_with_ef_kernel_matches_post():
    """overlap='fused' and overlap='post' share execute_bucket, so they
    agree bit-for-bit with the kernel engaged too."""
    post = _train("covap", "post", 5, use_ef_kernel=True)
    fused = _train("covap", "fused", 5, use_ef_kernel=True)
    _assert_tree_equal(post["params"], fused["params"])
    _assert_tree_equal(post["comp"], fused["comp"])


# ---------------------------------------------------------------------------
# pod-block helpers (satellite)
# ---------------------------------------------------------------------------

def test_strip_pod_block_asserts_local_block():
    good = {"w": jnp.zeros((1, 4, 4))}
    out = strip_pod_block(good)
    assert jax.tree.leaves(out)[0].shape == (4, 4)
    bad = {"w": jnp.zeros((2, 4, 4))}
    with pytest.raises(ValueError, match="pod block"):
        strip_pod_block(bad)
    # host-side use: peel pod 0 off a full state
    out = strip_pod_block(bad, expect_local=False)
    assert jax.tree.leaves(out)[0].shape == (4, 4)
