"""The command itself, without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.tiny import ROOT


def run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gpt2-paper.covap-i4.gb8.w1", "--seed", "3000000017", "--seconds",
         "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    out = run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "nothing was run" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_every_cell_names_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        for path in (("traffic", w["traffic"] + ".json"),
                     ("limits", w["name"] + ".json")):
            assert os.path.exists(os.path.join(ROOT, "bench", *path)), path
    for m in bench["per_layer"]:
        assert os.path.exists(
            os.path.join(ROOT, "bench", "metrics", m["name"] + ".py"))


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**40 + 3])
def test_batches_repeat_from_the_seed(seed):
    from bench import data

    a = data.ring(seed, batches=2, global_batch=4, seq_len=16, vocab=300)
    b = data.ring(seed, batches=2, global_batch=4, seq_len=16, vocab=300)
    for x, y in zip(a, b):
        assert (x["tokens"] == y["tokens"]).all()
        assert (x["labels"][:, :-1] == x["tokens"][:, 1:]).all()
        assert x["tokens"].max() < 300
    rows = [tuple(r) for x in a for r in x["tokens"]]
    assert len(set(rows)) == len(rows)


def test_host_report_finds_the_stretch_that_holds_a_stall():
    from bench import run as bench_run

    # 30 steps fetched 10 ms apart, a sync after step 0 and every 10th, and
    # a 2 s stall before step 15
    stamps = [0.01 * i + (2.0 if i >= 15 else 0.0) for i in range(30)]
    with bench_run.GcWatch() as gcw:
        pass
    line = bench_run.host_report(stamps, stamps[-1] + 0.01, 10, gcw)
    assert "[90.0, 2100.0, 100.0] ms, longest ending at step 20" in line
    assert "longest dispatch wait 2010.0 ms before step 15" in line


def test_readers_receive_the_configuration_and_the_shapes(monkeypatch):
    """A traced run hands every reader the configuration, the sequence
    length and the tokens one chip trains a step, beside the counts that
    ``bench/flops.py`` makes of them."""
    from bench import flops
    from bench import run as bench_run
    from bench import trace as T
    from bench.tests import tiny

    ops = [T.Op("%fusion.1 = f32[] fusion()", 0, 50)]
    T.set_self_times(ops)
    monkeypatch.setattr(T, "reduce", lambda tdir: T.Trace(
        {0: ops}, [("window", 0, 100)], (0, 100)))
    seen = {}
    monkeypatch.setattr(bench_run, "read_per_layer",
                        lambda listed, reduced, ctx: seen.update(ctx) or {})
    r = bench_run.run_cell(tiny.cell(), 2**31 + 29, 0.2, True,
                           tiny.devices(), tiny.PEAK)
    assert r["correct"], r["checks"]
    tokens = tiny.TRAFFIC["global_batch"] * tiny.TRAFFIC["seq_len"]
    assert seen["arch"] == tiny.ARCH
    assert seen["seq_len"] == tiny.TRAFFIC["seq_len"] == 64
    assert seen["tokens_per_chip_step"] == tokens == 512
    assert seen["steps"] == 8
    assert seen["ef_elements_per_step"] == flops.param_elements(tiny.ARCH)
    assert seen["model_flops_per_chip_step"] == \
        flops.train_flops_per_token(tiny.ARCH, 64) * tokens


def test_reader_context_divides_the_tokens_over_the_chips():
    from bench import run as bench_run
    from bench.tests.tiny import PEAK

    with open(os.path.join(ROOT, "bench", "configs", "gpt2-paper.json")) as f:
        arch = json.load(f)["config"]
    job = {"global_batch": 8, "seq_len": 1024}
    ctx = bench_run.reader_ctx(arch, job, 4, 2, PEAK)
    assert ctx["tokens_per_chip_step"] == 2048
    # 1,024,307,712 FLOPs a token, 2048 tokens a chip a step
    assert ctx["model_flops_per_chip_step"] == 1_024_307_712 * 2048
    assert ctx["ef_elements_per_step"] == 190_532_352


def test_a_configuration_that_cannot_be_counted_stops_the_set_up():
    from bench import run as bench_run
    from bench.tests import tiny

    with pytest.raises(ValueError, match="sliding_window"):
        bench_run.run_cell(tiny.cell(sliding_window=16), 2**31 + 31, 0.2,
                           False, tiny.devices(), tiny.PEAK)
