"""CI gate for the overlap execution engine (DESIGN.md §11).

Runs ``repro.launch.overlap_gate`` in a subprocess (the fake 8-device
count must be set before jax imports): it compiles one fused-overlap COVAP
train step and FAILS unless at least one bucket collective-start is
scheduled before the final gradient-producing fusion — i.e. unless the
compiled module really issues collectives inside the backward pass.
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
        [sys.executable, "-m", "repro.launch.overlap_gate"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("OVERLAP ")),
        "OVERLAP <missing>",
    )
    if r.returncode != 0:
        raise AssertionError(
            f"overlap interleaving gate failed: {line}\n{r.stderr[-2000:]}"
        )
    kv = dict(p.split("=") for p in line.split()[1:])
    return [
        row("overlap/collectives", 0.0, f"n={kv['num_collectives']}"),
        row(
            "overlap/before_final_grad", 0.0,
            f"n={kv['before_final_grad']};independent={kv['independent']}",
        ),
    ]
