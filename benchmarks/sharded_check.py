"""CI gate for sharded sync (DESIGN.md §13).

Runs ``repro.launch.sharded_gate`` in a subprocess (the fake 8-device
count must be set before jax imports): it compiles one fused sharded COVAP
train step and FAILS unless the compiled module reduce-scatters gradient
buckets before the final gradient-producing fusion AND schedules the
deferred param all-gathers at the step's head (where they overlap the
forward pass), and unless the schedule-level exposed wire bytes per worker
are <= 0.6x the all-reduce path's.
"""
from __future__ import annotations

import os
import subprocess
import sys

from .common import row

_SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src")
)


def run(smoke: bool = False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # fake CPU devices; the parent may hold a chip
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.sharded_gate"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("SHARDED ")),
        "SHARDED <missing>",
    )
    if r.returncode != 0:
        raise AssertionError(
            f"sharded placement gate failed: {line}\n{r.stderr[-2000:]}"
        )
    kv = dict(p.split("=") for p in line.split()[1:])
    return [
        row(
            "sharded/placement", 0.0,
            f"rs={kv['num_reduce_scatter']};ag={kv['num_all_gather']};"
            f"rs_before_final_grad={kv['rs_before_final_grad']};"
            f"ag_before_first_rs={kv['ag_before_first_rs']}",
        ),
        row("sharded/exposed_ratio", 0.0,
            f"ratio={kv['exposed_ratio']}"),
    ]
