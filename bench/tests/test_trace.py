"""Trace reduction and the per-layer readers, on hand-made intervals and on
a short trace of the gpt2-paper one-chip cell recorded on a TPU v5e."""
import gzip
import os
import shutil

import pytest

from bench import run
from bench import trace as T
from bench.metrics import (device_idle_pct, dispatch_idle_ms,
                           ef_kernel_roofline_pct, step_mfu, sync_ms)
from bench.tests.tiny import ROOT

RECORDED = os.path.join(ROOT, "bench", "testdata", "gpt2-w1")
SPANS = os.path.join(ROOT, "bench", "testdata", "gpt2-w1-spans")
QWEN_SPANS = os.path.join(ROOT, "bench", "testdata", "qwen-w1-spans")
CTX = {"steps": 1, "hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
       # gpt2-paper: 1,024,305,408 FLOPs a token, 8192 tokens a step, and
       # 190,532,352 gradient elements through the EF kernel a step
       "model_flops_per_chip_step": 1_024_305_408 * 8192,
       "ef_elements_per_step": 190_532_352}


def op(text, start, end, stack=""):
    return T.Op(text, start, end, stack)


def trace_of(*ops, window=(0, 100)):
    ops = list(ops)
    T.set_self_times(ops)
    return T.Trace({0: sorted(ops, key=lambda o: o.start)},
                   [("window", *window)], window)


def test_union_merges_and_clips():
    assert T.union([(5, 10), (0, 3), (2, 6), (20, 30)], 1, 25) == [
        (1, 10), (20, 25)]
    assert T.covered_ns([(0, 10), (5, 15)]) == 15


def test_subtract_and_gaps():
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.gaps([(10, 20), (30, 40)], 0, 50) == [(0, 10), (20, 30),
                                                    (40, 50)]


def test_self_time_leaves_out_nested_ops():
    loop = op("%while.1 = f32[] while(f32[] %a)", 0, 100)
    a = op("%fusion.1 = f32[] fusion(f32[] %a)", 10, 30)
    b = op("%fusion.2 = f32[] fusion(f32[] %b)", 40, 60)
    inner = op("%copy.3 = f32[] copy(f32[] %b)", 45, 50)
    T.set_self_times([loop, a, b, inner])
    assert (loop.self_ns, a.self_ns, b.self_ns, inner.self_ns) == (
        60, 20, 15, 5)
    assert (loop.opcode, a.name) == ("while", "fusion.1")


def test_busy_and_idle_are_unions_over_the_window():
    tr = trace_of(op("%f.1 = f32[] fusion()", 0, 40),
                  op("%f.2 = f32[] fusion()", 30, 50),
                  op("%f.3 = f32[] fusion()", 90, 120))
    assert T.busy_ns(tr, 0) == 60
    assert device_idle_pct.read(tr, CTX) == pytest.approx(40.0)


def test_breakdown_labels_gaps_by_host_span():
    tr = trace_of(op("%f.1 = f32[] fusion()", 0, 40),
                  op("%f.2 = f32[] fusion()", 70, 100))
    tr.spans.append(("batch_fetch", 45, 60))
    out = T.breakdown(tr)
    assert out["device_ops"][0] == ["f.1", 40e-9]
    assert out["idle_gaps"] == [["batch_fetch@chip0", 30e-9]]


def test_host_names_keep_the_bench_and_trainer_spans():
    assert [T.host_name(n) for n in (
        "bench.window", "train_step", "train.dispatch", "train_steps",
        "trainer", "jit_step_fn")] == [
        "window", "train_step", "train.dispatch", None, None, None]


def stepped():
    """One step as the trainer's host spans frame it, inside the window:
    the batch wait (with the benchmark's fetch in it), the dispatch (with a
    shorter ``train.*`` span nested in it) and the host sync; the device
    is idle over 25-45 and 60-80."""
    tr = trace_of(op("%f.1 = f32[] fusion()", 0, 25),
                  op("%f.2 = f32[] fusion()", 45, 60),
                  op("%f.3 = f32[] fusion()", 80, 100))
    tr.spans += [("trainer_run", 0, 100), ("train_step", 10, 90),
                 ("train.batch_wait", 12, 30), ("batch_fetch", 14, 28),
                 ("train.dispatch", 30, 50), ("train.inner", 31, 34),
                 ("train.host_sync", 50, 70)]
    return tr


def test_dispatch_idle_is_device_idle_under_the_innermost_wait_span():
    tr = stepped()
    # idle 25-45: batch wait 25-30 (the bench fetch inside it counts: it is
    # no trainer span), dispatch 30-31 and 34-45 (not 31-34, where a
    # shorter trainer span is innermost); idle 60-80: host sync, then
    # train_step alone
    assert dispatch_idle_ms.read(tr, CTX) == pytest.approx(17e-6)
    assert dispatch_idle_ms.read(tr, dict(CTX, steps=2)) == pytest.approx(
        8.5e-6)


def test_breakdown_labels_gaps_by_the_innermost_trainer_span():
    # each gap by the span open at its middle: 70 falls after the host
    # sync, where only the step's span is open
    assert T.breakdown(stepped())["idle_gaps"] == [
        ["train_step@chip0", 20e-9], ["train.dispatch@chip0", 20e-9]]


def test_step_mfu_is_model_flops_over_busy_time_at_peak():
    tr = trace_of(op("%f.1 = f32[] fusion()", 0, 60_000_000),
                  window=(0, 100_000_000))
    want = 100 * 1_024_305_408 * 8192 / (0.06 * 197e12)
    assert step_mfu.read(tr, CTX) == pytest.approx(want)


def test_sync_time_sums_ops_under_the_bucket_scopes():
    tr = trace_of(
        op("%ef_update.1 = f32[] custom-call()", 0, 10,
           "jit(step_fn)/covap_bucket_0/phase_0/jit(ef_update)/pallas_call"),
        op("%fusion.2 = f32[] fusion()", 10, 50, "jit(step_fn)/transformer"),
        op("%reshape.3 = f32[] reshape()", 50, 56,
           "jit(step_fn)/covap_arena_bucket_3/phase_1/reshape"))
    assert sync_ms.read(tr, CTX) == pytest.approx(16e-6)


EF = ("%ef_update.7 = (f32[1024,128]{1,0:T(8,128)}, f32[1024,128]{1,0:T(8,128)"
      "S(1)}) custom-call(f32[1024,128]{1,0:T(8,128)S(1)} %a, f32[1024,128]"
      "{1,0:T(8,128)} %b, f32[1]{0:T(128)} %c), custom_call_target="
      "\"tpu_custom_call\", operand_layout_constraints={f32[1024,128]{1,0}, "
      "f32[1024,128]{1,0}, f32[1]{0}}")


def test_ef_roofline_counts_only_the_arrays_in_hbm():
    n, share = ef_kernel_roofline_pct.hbm_share(EF)
    assert (n, share) == (131072, 0.5)
    # half of 16 B x 131072 over 819 GB/s is 1280.3 ns; the call took 2000
    tr = trace_of(op(EF, 0, 2000), window=(0, 5000))
    want = 100 * (8 * 131072 / 819e9) / 2000e-9
    ctx = dict(CTX, ef_elements_per_step=131072)
    assert ef_kernel_roofline_pct.read(tr, ctx) == pytest.approx(want)


def test_ef_roofline_leaves_out_the_block_padding():
    # the shapes need 120,000 elements; the call ran on 131072
    tr = trace_of(op(EF, 0, 2000), window=(0, 5000))
    want = 100 * (8 * 120_000 / 819e9) / 2000e-9
    ctx = dict(CTX, ef_elements_per_step=120_000)
    assert ef_kernel_roofline_pct.read(tr, ctx) == pytest.approx(want)


@pytest.mark.parametrize("elements", [131073, 100_000])
def test_ef_roofline_refuses_calls_that_do_not_fit_the_shapes(elements):
    tr = trace_of(op(EF, 0, 2000), window=(0, 5000))
    with pytest.raises(ValueError, match="padded elements"):
        ef_kernel_roofline_pct.read(tr, dict(CTX, ef_elements_per_step=elements))


@pytest.mark.parametrize("reader", [sync_ms, ef_kernel_roofline_pct,
                                    dispatch_idle_ms])
def test_missing_kernel_or_scope_reads_nothing(reader):
    tr = trace_of(op("%fusion.1 = f32[] fusion()", 0, 50))
    assert reader.read(tr, CTX) is None


def test_a_metric_that_reads_nothing_is_left_out():
    tr = trace_of(op("%fusion.1 = f32[] fusion()", 0, 50))
    listed = [{"name": "device_idle_pct", "unit": "%"},
              {"name": "ef_kernel_roofline_pct", "unit": "%"}]
    assert list(run.read_per_layer(listed, tr, CTX)) == ["device_idle_pct"]


def reduced(tmp_path_factory, src):
    """A recording under ``bench/testdata``, reduced; the ``.xplane.pb`` is
    kept gzipped."""
    out = tmp_path_factory.mktemp(os.path.basename(src))
    with gzip.open(os.path.join(src, "tpu.xplane.pb.gz")) as f:
        (out / "tpu.xplane.pb").write_bytes(f.read())
    shutil.copy(os.path.join(src, "tpu.trace.json.gz"), out)
    return T.reduce(str(out))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two steps of ``gpt2-paper.covap-i4.gb8.w1`` traced on one TPU v5e by
    the benchmark's own set-up, before the trainer had host spans."""
    return reduced(tmp_path_factory, RECORDED)


@pytest.fixture(scope="module")
def spanned(tmp_path_factory):
    """The same two steps recorded with the trainer's host spans."""
    return reduced(tmp_path_factory, SPANS)


@pytest.fixture(scope="module")
def qwen_spanned(tmp_path_factory):
    """Three steps of ``qwen1.5-0.5b.covap-i4.gb8.w1`` traced on one TPU v5e
    with the trainer's host spans, the last of them the log cadence's step."""
    return reduced(tmp_path_factory, QWEN_SPANS)


def test_recorded_trace_reduces(recorded):
    assert list(recorded.chips) == [0]
    assert recorded.window_ns > 0
    busy = T.busy_ns(recorded, 0)
    assert 0 < busy <= recorded.window_ns
    names = {n for n, _, _ in recorded.spans}
    assert {"window", "trainer_run", "batch_fetch"} <= names


def test_recorded_trace_metrics(recorded):
    ctx = dict(CTX, steps=2)
    # 3.97% idle (one host sync at the first step of the window), 6.84 ms
    # of sync a step, and 78.18% of roofline: the calls ran on 190,991,360
    # padded elements a step, the shapes need 190,532,352
    assert device_idle_pct.read(recorded, ctx) == pytest.approx(3.968, abs=1e-3)
    assert sync_ms.read(recorded, ctx) == pytest.approx(6.838, abs=1e-3)
    assert ef_kernel_roofline_pct.read(recorded, ctx) == pytest.approx(
        78.18, abs=1e-2)
    assert step_mfu.read(recorded, ctx) == pytest.approx(26.170, abs=1e-3)
    out = T.breakdown(recorded)
    assert len(out["device_ops"]) == 10 and out["idle_gaps"]


def test_recorded_trace_without_trainer_spans_reads_no_dispatch_idle(
        recorded):
    assert dispatch_idle_ms.read(recorded, dict(CTX, steps=2)) is None


def test_recorded_trainer_spans_are_kept(spanned):
    names = [n for n, _, _ in spanned.spans]
    counts = {n: names.count(n) for n in (
        "window", "trainer_run", "batch_fetch", "train_step",
        "train.batch_wait", "train.dispatch", "train.host_sync")}
    assert counts == {"window": 1, "trainer_run": 1, "batch_fetch": 2,
                      "train_step": 2, "train.batch_wait": 2,
                      "train.dispatch": 2, "train.host_sync": 1}


def test_recorded_dispatch_idle(spanned):
    ctx = dict(CTX, steps=2)
    # the device idles 2.61 ms at the window's start, while the first
    # step is dispatched, and 2.58 ms while the second is, after the host
    # sync of the first: 2.57 ms a step of 3.83% of a 338.7 ms window
    got = dispatch_idle_ms.read(spanned, ctx)
    assert got == pytest.approx(2.569, abs=1e-3)
    idle_ms = device_idle_pct.read(spanned, ctx) / 100 * spanned.window_ns \
        / 1e6 / ctx["steps"]
    assert 0 < got <= idle_ms
    assert [label for label, _ in T.breakdown(spanned)["idle_gaps"][:3]] == [
        "train.host_sync@chip0", "window@chip0", "train.dispatch@chip0"]


def test_recorded_qwen_dispatch_idle(qwen_spanned):
    ctx = dict(CTX, steps=3)
    names = [n for n, _, _ in qwen_spanned.spans]
    assert [names.count(n) for n in ("train_step", "train.batch_wait",
                                     "train.dispatch", "train.host_sync")] \
        == [3, 3, 3, 1]
    # the host sync of the third step idles the device longest (7.90 ms),
    # then two dispatch waits (4.44 and 2.57 ms): 3.47 ms a step of the
    # 5.77 that the device idles in a 467.1 ms window
    got = dispatch_idle_ms.read(qwen_spanned, ctx)
    assert got == pytest.approx(3.474, abs=1e-3)
    idle_ms = device_idle_pct.read(qwen_spanned, ctx) / 100 \
        * qwen_spanned.window_ns / 1e6 / ctx["steps"]
    assert idle_ms == pytest.approx(5.773, abs=1e-3)
    assert 0 < got <= idle_ms
    assert [label for label, _ in T.breakdown(qwen_spanned)["idle_gaps"][:3]] \
        == ["train.host_sync@chip0", "train.dispatch@chip0",
            "train.dispatch@chip0"]
