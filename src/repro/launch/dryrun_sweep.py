"""Crash-isolated dry-run sweep driver.

XLA CHECK failures (compiler bugs on exotic sharding combos) abort the whole
process, so each (arch, shape, mesh) combo runs in its own subprocess with a
timeout; crashes/timeouts are recorded as JSON failure records instead of
killing the sweep.

  python -m repro.launch.dryrun_sweep --out experiments/dryrun --mesh both
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.configs import INPUT_SHAPES, list_archs


def run_combo(arch, shape, mesh_tag, compressor, interval, out_dir, timeout):
    tag = f"{arch}__{shape}__{mesh_tag}__{compressor}"
    path = os.path.join(out_dir, tag + ".json")
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--mesh", mesh_tag,
        "--compressor", compressor, "--out", out_dir,
    ]
    if interval is not None:
        cmd += ["--interval", str(interval)]
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},  # compile-only
        )
        crashed = proc.returncode != 0 and not os.path.exists(path)
        if crashed:
            rec = {
                "arch": arch, "shape": shape, "mesh": mesh_tag,
                "compressor": compressor, "status": "crash",
                "returncode": proc.returncode,
                "stderr_tail": proc.stderr[-3000:],
                "wall_s": round(time.perf_counter() - t0, 1),
            }
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            return "CRASH", tag
    except subprocess.TimeoutExpired:
        rec = {
            "arch": arch, "shape": shape, "mesh": mesh_tag,
            "compressor": compressor, "status": "timeout",
            "timeout_s": timeout,
        }
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return "TIMEOUT", tag
    try:
        with open(path) as f:
            rec = json.load(f)
        return ("OK" if rec.get("status") == "ok" else "FAIL"), tag
    except FileNotFoundError:
        return "MISSING", tag


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2", "both"])
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--compressor", default="covap")
    ap.add_argument("--interval", type=int, default=None)
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = (
        list_archs(assigned_only=True) if args.arch == "all" else args.arch.split(",")
    )
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"pod1": ["pod1"], "pod2": ["pod2"], "both": ["pod1", "pod2"]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_tag in meshes:
                tag = f"{arch}__{shape}__{mesh_tag}__{args.compressor}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    try:
                        with open(path) as f:
                            st = json.load(f).get("status")
                    except Exception:
                        st = None
                    if st == "ok":
                        print(f"skip {tag}", flush=True)
                        continue
                status, tag = run_combo(
                    arch, shape, mesh_tag, args.compressor,
                    args.interval, args.out, args.timeout,
                )
                print(f"{status:8s} {tag}", flush=True)
                results.append((status, tag))
    bad = [t for s, t in results if s not in ("OK",)]
    print(f"\n{len(results)} run, {len(bad)} not-OK")
    for t in bad:
        print("  ", t)


if __name__ == "__main__":
    main()
