"""The readers of the program's own name scopes (``forward_ms``,
``backward_ms``, ``attention_ms``, ``optimizer_ms``) on hand-made traces,
on the older recording of a program without those scopes
(``bench/testdata/gpt2-w1``), and on a recording of gpt2-paper's one-chip
cell that has them (``bench/testdata/gpt2-w1-spans``)."""
import gzip
import os
import shutil

import pytest

from bench import scopes
from bench import trace as T
from bench.metrics import (attention_ms, backward_ms, device_idle_pct,
                           ef_kernel_roofline_pct, forward_ms, optimizer_ms,
                           step_mfu, sync_ms)
from bench.tests.test_trace import CTX, op, trace_of
from bench.tests.tiny import ROOT

READERS = (forward_ms, backward_ms, attention_ms, optimizer_ms)
FWD = "jit(step_fn)/jvp(model)/while/body/closed_call"
BWD = "jit(step_fn)/transpose(jvp(model))/while/body/closed_call"
REMAT = BWD + "/checkpoint/rematted_computation"


def stacked(*pairs):
    """A one-chip trace of back-to-back 10 ns ops with the given stacks."""
    return trace_of(*(op(f"%f.{i} = f32[] fusion()", 10 * i, 10 * i + 10,
                         stack) for i, stack in enumerate(pairs)),
                    window=(0, 10 * len(pairs)))


def ms(ns):
    return ns / 1e6


def test_scope_strips_transform_wrappers():
    assert [scopes.scope(s) for s in (
        "jvp(model)", "transpose(jvp(model))", "attention", "while",
        "transpose(jvp(covap_bucket_3")] == [
        "model", "model", "attention", "while", "covap_bucket_3"]


def test_forward_leaves_out_the_backward_and_its_recompute():
    tr = stacked(FWD + "/dot_general", BWD + "/dot_general",
                 REMAT + "/attention/while/body/exp", FWD + "/add")
    assert forward_ms.read(tr, CTX) == pytest.approx(ms(20))


def test_backward_holds_the_recompute_and_leaves_out_bucket_scopes():
    tr = stacked(BWD + "/dot_general", REMAT + "/dot_general",
                 BWD + "/covap_bucket_2/phase_0/jit(ef_update)/pallas_call",
                 "jit(step_fn)/transpose(jvp(covap_bucket_0/phase_0))/add",
                 FWD + "/dot_general")
    assert backward_ms.read(tr, CTX) == pytest.approx(ms(20))
    # the bucket ops are sync_ms's, so no op is read twice
    assert sync_ms.read(tr, CTX) == pytest.approx(ms(20))


def test_attention_counts_both_passes_and_whole_segments_only():
    tr = stacked(FWD + "/attention/while/body/exp",
                 REMAT + "/attention/while/body/exp",
                 BWD + "/attention/while/body/dot_general",
                 FWD + "/attention_out/dot_general",
                 FWD + "/dot_general")
    assert attention_ms.read(tr, CTX) == pytest.approx(ms(30))


def test_optimizer_reads_its_scope_and_a_named_segment_only():
    tr = stacked("jit(step_fn)/optimizer/add", "jit(step_fn)/optimizer/sqrt",
                 "jit(step_fn)/jvp(optimizers)/add", "jit(step_fn)/add:")
    assert optimizer_ms.read(tr, CTX) == pytest.approx(ms(20))


def test_scope_readers_average_chips_and_steps():
    a = [op("%f.1 = f32[] fusion()", 0, 40, FWD + "/x")]
    b = [op("%f.1 = f32[] fusion()", 0, 20, FWD + "/x")]
    for ops in (a, b):
        T.set_self_times(ops)
    tr = T.Trace({0: a, 1: b}, [("window", 0, 100)], (0, 100))
    assert forward_ms.read(tr, dict(CTX, steps=2)) == pytest.approx(ms(15))


@pytest.mark.parametrize("reader", READERS)
def test_a_scope_reader_without_its_scope_reads_nothing(reader):
    tr = stacked("jit(step_fn)/jvp()/while/body/dot_general",
                 "jit(step_fn)/transpose(jvp())/while/body/dot_general",
                 "jit(step_fn)/add:", "")
    assert reader.read(tr, CTX) is None


def load(name):
    """A recorded trace under ``bench/testdata/<name>``, reduced."""
    def fixture(tmp_path_factory):
        src = os.path.join(ROOT, "bench", "testdata", name)
        out = tmp_path_factory.mktemp(name)
        with gzip.open(os.path.join(src, "tpu.xplane.pb.gz")) as f:
            (out / "tpu.xplane.pb").write_bytes(f.read())
        shutil.copy(os.path.join(src, "tpu.trace.json.gz"), out)
        return T.reduce(str(out))
    return pytest.fixture(scope="module")(fixture)


unscoped = load("gpt2-w1")


@pytest.mark.parametrize("reader", READERS)
def test_a_program_without_the_scopes_leaves_the_new_metrics_out(
        unscoped, reader):
    # a program without these scopes, as recorded on the chip: the scope
    # readers find nothing, and the metric is left out
    assert reader.read(unscoped, dict(CTX, steps=2)) is None


def test_the_older_readers_are_unchanged_on_the_unscoped_recording(unscoped):
    ctx = dict(CTX, steps=2)
    assert device_idle_pct.read(unscoped, ctx) == pytest.approx(3.968,
                                                                abs=1e-3)
    assert sync_ms.read(unscoped, ctx) == pytest.approx(6.838, abs=1e-3)
    assert ef_kernel_roofline_pct.read(unscoped, ctx) == pytest.approx(
        78.18, abs=1e-2)
    assert step_mfu.read(unscoped, ctx) == pytest.approx(26.170, abs=1e-3)


scoped = load("gpt2-w1-spans")


def test_the_scoped_recording_reads_the_split(scoped):
    # two steps of gpt2-paper.covap-i4.gb8.w1 recorded on one TPU v5e with
    # the scopes in place: forward, backward (remat recompute in),
    # attention inside both, and AdamW; with the sync they hold 93.7% of the
    # busy time, and the older readers read as on the unscoped recording
    ctx = dict(CTX, steps=2)
    got = {r.__name__.rsplit(".", 1)[-1]: r.read(scoped, ctx)
           for r in READERS + (sync_ms,)}
    assert got == pytest.approx({
        "forward_ms": 28.452, "backward_ms": 110.214, "attention_ms": 75.645,
        "optimizer_ms": 7.068, "sync_ms": 6.840}, abs=1e-3)
    busy_ms = T.busy_ns(scoped, 0) / 2 / 1e6
    covered = sum(v for k, v in got.items() if k != "attention_ms")
    assert covered / busy_ms == pytest.approx(0.9367, abs=1e-4)
    assert ef_kernel_roofline_pct.read(scoped, ctx) == pytest.approx(
        78.07, abs=1e-2)
    assert step_mfu.read(scoped, ctx) == pytest.approx(26.151, abs=1e-3)


def test_the_scoped_recording_holds_the_trainer_host_spans():
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(ROOT, "bench", "testdata", "gpt2-w1-spans",
                                "tpu.xplane.pb.gz")) as f:
        data = ProfileData.from_serialized_xspace(f.read())
    names = [ev.name for plane in data.planes
             if plane.name.startswith("/host") for line in plane.lines
             for ev in line.events]
    counts = {n: names.count(n) for n in ("train_step", "train.batch_wait",
                                          "train.dispatch", "train.host_sync")}
    # two steps; the trainer syncs the host on the first step of a call
    assert counts == {"train_step": 2, "train.batch_wait": 2,
                      "train.dispatch": 2, "train.host_sync": 1}
