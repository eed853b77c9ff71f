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
