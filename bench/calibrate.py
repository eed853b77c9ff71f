"""The readings that a cell's limits are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 12 [--control 3]
        [--faults unchanged,half_batch --fault-seeds 3] [--first-seed N]

In one process, for each seed: the program's first steps through the
benchmark's own set-up (``run.Job``) against the plain reference; the
control (the reference in float8 put in the program's place) against the
reference on the first ``--control`` seeds; and the program with each
planted fault of ``bench/faults.py`` on the first ``--fault-seeds`` seeds.
Each reading is printed as one JSON line, with the verdict that
``correctness.judge`` gives it under the cell's committed limits.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import correctness, faults
    from bench.run import Job, load_cell, tpu_devices
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    cell = load_cell(args.workload)
    devices = tpu_devices(cell["chips"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reference = importlib.import_module(cell["reference"])
    arch = cell["config"]["config"]
    train = dict(cell["traffic"]["optimizer"],
                 interval=cell["traffic"]["interval"])

    def program(seed, fault=None):
        with faults.planted(fault):
            job = Job(cell, seed, devices, reference)
            prog = job.first_cycle(3)
            host = job.host_ring[:3]
            del job
            gc.collect()
        return prog, host

    def emit(kind, seed, got, ref, **extra):
        """The compared numbers, and per step and per leaf what they are
        the largest of."""
        leaves = {q: correctness.leaf_gaps(got[q], ref[q], ref[q])
                  for q in ("grad1", "change", "resid")}
        steps = [abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                       ref["losses"])]
        nums = correctness.numbers(got, ref)
        ok, _ = correctness.judge(nums, cell["limits"])
        print(json.dumps({"kind": kind, "seed": seed, "correct": ok, **nums,
                          "loss_steps": steps, "leaves": leaves, **extra}),
              flush=True)

    fault_names = [f for f in args.faults.split(",") if f]
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t = time.perf_counter()
        prog, host = program(seed)
        ref = reference.run(arch, train, seed, host)
        emit("program", seed, prog, ref, seconds=time.perf_counter() - t,
             losses=prog["losses"], ref_losses=ref["losses"])
        if i < args.control:
            ctl = reference.run(arch, train, seed, host,
                                matmul_dtype="float8")
            emit("control", seed, ctl, ref)
        if i < args.fault_seeds:
            for fault in fault_names:
                bad, _ = program(seed, fault)
                emit(fault, seed, bad, ref)
        del ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
