"""CI gate for two-level hierarchical sharded sync (DESIGN.md §17).

Runs ``repro.launch.hier_gate`` in a subprocess (the fake 8-device count
must be set before jax imports): it compiles one hierarchical sharded
COVAP train step on a (pod=2, data=4) mesh and FAILS unless the per-link
bytes of the statically planned ``CommSchedule`` (intra-pod gradient
reduce-scatters + deferred head all-gather on the ICI, owned-shard
cross-pod exchanges on the DCN) match the compiled HLO's replica-group-
classified collective bytes.  The reported ``hier_exposed_dcn_ratio``
lands in the BENCH snapshot under the trajectory gate.
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
        [sys.executable, "-m", "repro.launch.hier_gate"],
        capture_output=True, text=True, timeout=560, env=env,
    )
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("HIER ")),
        "HIER <missing>",
    )
    if r.returncode != 0:
        raise AssertionError(
            f"hierarchical per-link byte gate failed: {line}\n{r.stderr[-2000:]}"
        )
    kv = dict(p.split("=") for p in line.split()[1:])
    return [
        row(
            "hier/bytes_by_link", 0.0,
            f"ici_schedule={kv['ici_schedule']};ici_hlo={kv['ici_hlo']};"
            f"dcn_schedule={kv['dcn_schedule']};dcn_hlo={kv['dcn_hlo']};"
            f"match={kv['match']}",
        ),
        row("hier/exposed_dcn_ratio", 0.0,
            f"ratio={kv['hier_exposed_dcn_ratio']}"),
    ]
