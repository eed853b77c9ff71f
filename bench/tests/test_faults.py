"""The whole run but the look for a chip, with the timed path broken
underneath: ``correct`` comes out false for each fault a training cell can
have, and true without one."""
import json
import os
import subprocess
import sys

import pytest

from bench import faults, run
from bench.tests import tiny


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "no_feedback"])
def test_fault_on_one_chip(fault):
    with faults.planted(fault):
        r = run.run_cell(tiny.cell(), 2**31 + 17, 0.2, False, tiny.devices(),
                         tiny.PEAK)
    assert r["correct"] == (fault is None), r["checks"]


FOUR = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
from bench import faults, run
from bench.tests import tiny
out = {{}}
for fault in (None, "no_exchange", "half_batch", "no_feedback"):
    with faults.planted(fault):
        r = run.run_cell(tiny.cell(chips=4), 2**31 + 23, 0.2, False,
                         tiny.devices(4), tiny.PEAK)
    out[str(fault)] = r["correct"]
print(json.dumps(out))
"""


def test_faults_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", FOUR.format(root=tiny.ROOT)],
                         env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"None": True, "no_exchange": False, "half_batch": False,
                   "no_feedback": False}
