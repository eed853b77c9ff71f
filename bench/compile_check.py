"""Compile each cell's phase-0 training step for a described TPU v5e, on a
machine without one, and print the memory it would need.

    JAX_PLATFORMS=cpu python bench/compile_check.py [cell ...]

A rehearsal before the chip: the TPU compiler refuses here, at no chip
time, a kernel it cannot lower or a program that does not fit.  Off the
chip the backend is the CPU, so the Pallas kernels' interpret switch is
turned off by hand to compile the path the chip runs.  Nothing runs.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def compile_cell(name: str, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.run import load_cell
    from repro.configs import get_config
    from repro.kernels import common, ef_covap, pack_ef_cast
    from repro.models import build_model
    from repro.optim import adamw, cosine_warmup
    from repro.train.trainer import Trainer, TrainConfig

    for mod in (common, ef_covap, pack_ef_cast):
        mod.INTERPRET = False
    cell = load_cell(name)
    arch, job = cell["config"]["config"], cell["traffic"]
    opt = job["optimizer"]
    model = build_model(get_config(cell["config"]["arch"]).with_(**arch))
    optimizer = adamw(cosine_warmup(opt["lr"], opt["warmup_steps"],
                                    opt["total_steps"]),
                      b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
    tc = TrainConfig(compressor=job["compressor"], interval=job["interval"],
                     log_every=job["log_every"])
    mesh = Mesh(np.array(topo.devices[:cell["chips"]]), ("data",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    if cell["chips"] > 1:
        tr = Trainer(model, optimizer, tc, mesh=mesh, dp_axes=("data",))
    else:
        tr = Trainer(model, optimizer, tc)
    params = tr._shapes
    opt_state = jax.eval_shape(optimizer.init, params)
    comp = jax.eval_shape(lambda p: tr.compressor.init_state(p, tr.plan),
                          params)

    def place(tree, sharding):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), tree)

    batch = {k: jax.ShapeDtypeStruct((job["global_batch"], job["seq_len"]),
                                     jnp.int32, sharding=rows)
             for k in ("tokens", "labels")}
    compiled = tr._phase_fn(0).lower(
        place(params, rep), place(opt_state, rep), place(comp, rep), batch,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    return {
        "cell": name,
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "ef_kernel": "tpu_custom_call" in text,
        "all_reduces": text.count("all-reduce-start(") + text.count(
            " all-reduce("),
    }


def main(argv=None) -> int:
    from jax.experimental import topologies

    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in json.load(open(os.path.join(
            ROOT, "BENCHMARK.json")))["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        print(json.dumps(compile_cell(name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
