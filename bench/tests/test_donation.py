"""The harness under a step that donates its state: every fault still
reads ``correct`` false and the sound run true, the ``unchanged`` fault
hands the donated buffers back without a copy, and the check's change,
read leaf by leaf, reads as the tree of differences did."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import correctness, faults, run
from bench.references import decoder
from bench.tests import tiny


def job(seed):
    return run.Job(tiny.cell(), seed, tiny.devices(), decoder)


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "no_feedback"])
def test_fault_on_one_chip_under_donation(fault):
    with tiny.donating(), faults.planted(fault):
        r = run.run_cell(tiny.cell(), 2**31 + 41, 0.2, False, tiny.devices(),
                         tiny.PEAK)
    assert r["correct"] == (fault is None), r["checks"]


@pytest.mark.parametrize("fault", [None, "unchanged"])
def test_the_step_donates_its_state(fault):
    with tiny.donating(), faults.planted(fault):
        j = job(2**31 + 43)
        given = jax.tree.leaves(j.state["params"])
        where = [x.unsafe_buffer_pointer() for x in given]
        j.train(1)
    assert all(x.is_deleted() for x in given)
    if fault == "unchanged":
        # the step returned its own input buffers: no copy of the state
        kept = [x.unsafe_buffer_pointer()
                for x in jax.tree.leaves(j.state["params"])]
        assert kept == where


def test_change_is_read_leaf_by_leaf_as_the_tree_of_differences_read_it():
    seed = 2**31 + 47
    j = job(seed)
    j.train(3)
    start = decoder.init_params(tiny.ARCH, seed, out_shardings=j.rep)
    new = correctness.change_norms(j.state["params"], start)
    old = correctness.leaf_norms(jax.tree.map(
        lambda a, b: a - b, j.state["params"], start))
    assert list(new) == list(old)
    assert new == pytest.approx(old, rel=1e-6, abs=0)
    train = dict(tiny.TRAFFIC["optimizer"], interval=tiny.TRAFFIC["interval"])
    ref = decoder.run(tiny.ARCH, train, seed, j.host_ring[:3])["change"]
    assert correctness.worst_leaf(new, ref, ref) == pytest.approx(
        correctness.worst_leaf(old, ref, ref), rel=1e-5, abs=1e-9)


def test_change_norms_refuse_trees_that_differ():
    with pytest.raises(ValueError, match="structure"):
        correctness.change_norms({"a": jax.numpy.ones(2)},
                                 {"b": jax.numpy.ones(2)})


FOUR = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
import jax
from bench import faults, run
from bench.references import decoder
from bench.tests import tiny
out = {{}}
with tiny.donating():
    j = run.Job(tiny.cell(chips=4), 2**31 + 53, tiny.devices(4), decoder)
    given = jax.tree.leaves(j.state["params"])
    j.train(1)
    out["donated"] = all(x.is_deleted() for x in given)
    del j
    for fault in (None, "no_exchange", "unchanged", "half_batch",
                  "no_feedback"):
        with faults.planted(fault):
            r = run.run_cell(tiny.cell(chips=4), 2**31 + 59, 0.2, False,
                             tiny.devices(4), tiny.PEAK)
        out[str(fault)] = r["correct"]
print(json.dumps(out))
"""


def test_faults_on_four_devices_under_donation():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", FOUR.format(root=tiny.ROOT)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"donated": True, "None": True, "no_exchange": False,
                   "unchanged": False, "half_batch": False,
                   "no_feedback": False}
