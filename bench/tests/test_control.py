"""The control: the reference computed in float8 in the program's place
comes out not correct under the cells' limits."""
import pytest

from bench import correctness, data
from bench.references import decoder
from bench.tests import tiny


@pytest.mark.parametrize("seed", [2**31 + 3, 2**31 + 9001, 2**33 + 5])
def test_float8_control_is_not_correct(seed):
    arch, job = tiny.ARCH, tiny.TRAFFIC
    host = data.ring(seed, batches=3, global_batch=job["global_batch"],
                     seq_len=job["seq_len"], vocab=arch["vocab_size"],
                     **job["corpus"])
    train = dict(job["optimizer"], interval=job["interval"])
    ref = decoder.run(arch, train, seed, host)
    ctl = decoder.run(arch, train, seed, host, matmul_dtype="float8")
    ok, checks = correctness.judge(correctness.numbers(ctl, ref),
                                   tiny.LIMITS)
    assert not ok, checks
