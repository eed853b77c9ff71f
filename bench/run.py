"""On-chip benchmark of the COVAP trainer: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``bench/configs/<config>.json``, with the plain reference
module it names under ``bench/references/``) and a training job
(``bench/traffic/<traffic>.json``), held to the limits of
``bench/limits/<cell>.json``.  Per-layer metrics are read by
``bench/metrics/<metric>.py``.  Nothing here names a cell.

Set-up, timed as ``setup_s`` from process start: refuse a device that is
not a TPU, or fewer chips than the cell asks for; turn on the persistent
compilation cache; build the model and the ``Trainer``; make the weights on
the device from the seed; place a ring of distinct seeded batches on the
device (sharded over ``data`` on several chips); run one phase cycle one
step per ``Trainer.run`` call (this compiles the phase executables and
takes the program's readings for the check); time one more phase cycle.
With ``--trace 0`` the window is then as many whole phase cycles as fill
``--seconds``, in one ``Trainer.run`` call between two
``block_until_ready``s; with ``--trace 1`` it is two phase cycles under the
profiler, reduced in this process.  After the window the program's state is
freed and the reference trains the same first steps, which decides
``correct``.  The last line of standard output is the result as JSON; the
numbers compared, each beside its limit, are also the last lines of
standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise write its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench.flops import param_elements, train_flops_per_token  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell's entry of BENCHMARK.json with its files read."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}

    def read(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    config = read(configs[cell["config"]]["file"])
    return {
        "name": name,
        "chips": cell["chips"],
        "config": config,
        "traffic": read("bench", "traffic", f"{cell['traffic']}.json"),
        "limits": read("bench", "limits", f"{name}.json"),
        "reference": f"bench.references.{config['reference']}",
        "end_to_end": bench["end_to_end"],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def tpu_devices(chips: int):
    """The cell's chips, or exit non-zero before any result is printed."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu" or len(devices) < chips:
        log(f"bench: needs {chips} TPU chip(s), JAX found {len(devices)} "
            f"{dev.platform!r} device(s); nothing was run")
        raise SystemExit(2)
    return devices[:chips]


class Job:
    """The program under test, set up for one cell: the ``Trainer``, its
    state and the feed of device batches."""

    def __init__(self, cell: dict, seed: int, devices, reference):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from bench import data
        from repro.configs import get_config
        from repro.models import build_model
        from repro.optim import adamw, cosine_warmup
        from repro.train.trainer import Trainer, TrainConfig

        arch, job = cell["config"]["config"], cell["traffic"]
        opt = job["optimizer"]
        if opt["name"] != "adamw":
            raise ValueError(f"unsupported optimizer {opt['name']!r}")
        self.arch, self.job, self.seed = arch, job, seed
        self.reference = reference
        cfg = get_config(cell["config"]["arch"]).with_(**arch)
        model = build_model(cfg)
        optimizer = adamw(
            cosine_warmup(opt["lr"], opt["warmup_steps"], opt["total_steps"]),
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
        tc = TrainConfig(compressor=job["compressor"], interval=job["interval"],
                         log_every=job["log_every"])
        self.mesh = None
        if len(devices) > 1:
            self.mesh = Mesh(np.array(devices), ("data",))
            self.tr = Trainer(model, optimizer, tc, mesh=self.mesh,
                              dp_axes=("data",))
            self.rep = NamedSharding(self.mesh, P())
            rows = NamedSharding(self.mesh, P("data"))
        else:
            self.tr = Trainer(model, optimizer, tc)
            self.rep = rows = jax.sharding.SingleDeviceSharding(devices[0])
        self.devices = devices

        want = jax.tree.map(lambda s: (s.shape, s.dtype), self.tr._shapes)
        params = reference.init_params(arch, seed, out_shardings=self.rep)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if got != want:
            raise ValueError("the reference's parameter tree does not match "
                             "the model's")
        tr = self.tr
        opt_state, comp = jax.jit(
            lambda p: (tr.optimizer.init(p),
                       tr.compressor.init_state(p, tr.plan)),
            out_shardings=self.rep)(params)
        self.state = {"params": params, "opt": opt_state, "comp": comp,
                      "step": 0}

        self.host_ring = data.ring(
            seed, batches=job["ring_batches"],
            global_batch=job["global_batch"], seq_len=job["seq_len"],
            vocab=arch["vocab_size"], **job["corpus"])
        self.ring = [jax.device_put(b, rows) for b in self.host_ring]
        self.stamps = None      # host clock at each batch fetch, when a list
        self.feed = self._feed()

    def _feed(self):
        import jax

        i = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.batch_fetch"):
                batch = self.ring[i % len(self.ring)]
                if self.stamps is not None:
                    self.stamps.append(time.perf_counter())
            i += 1
            yield batch

    @property
    def phases(self) -> int:
        return self.tr.num_phases

    def train(self, steps: int) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.trainer_run"):
            self.state = self.tr.run(self.state, self.feed, steps=steps,
                                     log=None)

    def block(self) -> None:
        import jax

        jax.block_until_ready(
            [self.state[k] for k in ("params", "opt", "comp")])

    def first_cycle(self, compared: int) -> dict:
        """One phase cycle, one step per ``Trainer.run`` call, with the
        readings of the first ``compared`` steps."""
        from bench.correctness import change_norms, leaf_norms

        b1 = self.job["optimizer"]["b1"]
        losses, out = [], {}
        for step in range(self.phases):
            self.train(1)
            losses.append(self.tr.history[-1]["total_loss"])
            if step == 0:
                out["grad1"] = {k: v / (1 - b1) for k, v in
                                leaf_norms(self.state["opt"]["m"]).items()}
            if step == compared - 1:
                start = self.reference.init_params(self.arch, self.seed,
                                                   out_shardings=self.rep)
                out["change"] = change_norms(self.state["params"], start)
                out["resid"] = leaf_norms(self.chip_mean(self.state["comp"]))
                del start
        out["losses"] = losses[:compared]
        return out

    def chip_mean(self, tree):
        """The mean over chips of a tree that the trainer declares
        replicated but whose copies differ: each chip's error-feedback
        residual is of its own share of the batch, and their mean is the
        residual of the whole batch, which the reference keeps."""
        if self.mesh is None:
            return tree
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        order = {d: i for i, d in enumerate(self.mesh.devices.flat)}
        rows = NamedSharding(self.mesh, P("data"))

        def stack(x):
            shards = sorted(x.addressable_shards, key=lambda s: order[s.device])
            return jax.make_array_from_single_device_arrays(
                (len(shards),) + x.shape, rows, [s.data[None] for s in shards])

        return jax.jit(lambda t: jax.tree.map(lambda a: a.mean(0), t),
                       out_shardings=self.rep)(jax.tree.map(stack, tree))

    def compiles(self) -> int:
        return sum(fn._cache_size() for fn in self.tr._steps.values())

    def memory_peak(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)


def reader_ctx(arch: dict, job: dict, chips: int, steps: int,
               peak: dict) -> dict:
    """What every per-layer reader is handed beside the reduced trace."""
    tokens = job["global_batch"] * job["seq_len"] // chips
    return {
        "steps": steps, "arch": arch, "seq_len": job["seq_len"],
        "tokens_per_chip_step": tokens,
        "hbm_bytes_per_s": peak["hbm_bytes_per_s"],
        "bf16_flops": peak["bf16_flops"],
        "ef_elements_per_step": param_elements(arch),
        "model_flops_per_chip_step":
            train_flops_per_token(arch, job["seq_len"]) * tokens,
    }


def read_per_layer(listed: list, reduced, ctx: dict) -> dict:
    """Each listed metric by its reader, ``bench/metrics/<name>.py``,
    called as ``read(reduced, ctx)``.  A reader that finds nothing to read
    returns ``None``, and the metric is left out of the result: it is never
    reported as 0.  ``ctx`` (``reader_ctx``) holds:

    * ``steps``: the training steps in the traced window;
    * ``arch``: the configuration dict (``bench/configs/<name>.json``'s
      ``config``), whose shapes ``bench/flops.py`` counts;
    * ``seq_len``: the sequence length of the cell's job;
    * ``tokens_per_chip_step``: the tokens one chip trains a step;
    * ``hbm_bytes_per_s``, ``bf16_flops``: the chip's peaks
      (``bench/peaks.py``);
    * ``ef_elements_per_step``: the gradient elements the error-feedback
      kernel must stream a step (``flops.param_elements``);
    * ``model_flops_per_chip_step``: the model FLOPs one chip does a step
      (``flops.train_flops_per_token`` times ``tokens_per_chip_step``).
    """
    out = {}
    for m in listed:
        reader = load_module(
            os.path.join(ROOT, "bench", "metrics", f"{m['name']}.py"),
            f"bench_metric_{m['name']}")
        value = reader.read(reduced, ctx)
        if value is None:
            log(f"[trace] {m['name']} found nothing to read; left out")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class GcWatch:
    """Pauses of Python's garbage collector while the block runs."""

    def __enter__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._cb)
        return self

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def host_report(stamps: list, end: float, log_every: int, gcw: GcWatch) -> str:
    """Where the host's time in the window went: the stretches between the
    trainer's host syncs (after the first step and every ``log_every``-th),
    the longest wait between two dispatches with no sync between them, and
    the collector's pauses.  A stall in the window shows here as one long
    stretch, and as a long dispatch wait or pause when the host caused it."""
    n = len(stamps)
    synced = [i for i in range(n) if i == 0 or (i + 1) % log_every == 0]
    marks = [stamps[i + 1] if i + 1 < n else end for i in synced]
    spans = [b - a for a, b in zip(marks, marks[1:])]
    waits = [(stamps[i + 1] - stamps[i], i + 1) for i in range(n - 1)
             if i not in synced]
    longest = max(range(len(spans)), key=spans.__getitem__) if spans else None
    wait, at = max(waits, default=(0.0, 0))
    return (f"[host] sync stretches of {log_every} steps: "
            f"{[round(x * 1e3, 1) for x in spans]} ms"
            + (f", longest ending at step {synced[longest + 1] + 1}"
               if longest is not None else "")
            + f"; longest dispatch wait {wait * 1e3:.1f} ms before step {at}"
            f"; gc {len(gcw.pauses)} pauses "
            f"(gen2 {sum(g == 2 for g, _ in gcw.pauses)}), "
            f"{sum(d for _, d in gcw.pauses) * 1e3:.1f} ms, longest "
            f"{max((d for _, d in gcw.pauses), default=0.0) * 1e3:.1f} ms")


def device_info(devices, **extra) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), **extra}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             peak: dict, compared: int = 3) -> dict:
    """Set up, measure, check.  Returns the result line as a dict."""
    import jax

    from bench import correctness
    from bench import trace as tracing
    from repro.launch.compile_cache import enable_compile_cache

    # refuses, before anything is built, a configuration it cannot count
    flops = train_flops_per_token(cell["config"]["config"],
                                  cell["traffic"]["seq_len"])
    log(f"[cache] {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reference = importlib.import_module(cell["reference"])
    job = Job(cell, seed, devices, reference)
    phases = job.phases
    prog = job.first_cycle(compared)
    job.block()
    t = time.perf_counter()
    job.train(phases)
    job.block()
    step_s = (time.perf_counter() - t) / phases
    compiled = job.compiles()
    log(f"[setup] {phases} phase executables; warm step {step_s * 1e3:.3f} ms")

    tokens_per_step = job.job["global_batch"] * job.job["seq_len"]
    metrics, extra, result = {}, {}, {}
    if trace:
        steps = 2 * phases
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.window"):
                job.train(steps)
                job.block()
            jax.profiler.stop_trace()
            reduced = tracing.reduce(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ctx = reader_ctx(job.arch, job.job, len(devices), steps, peak)
        metrics = read_per_layer(cell["per_layer"], reduced, ctx)
        busy = [tracing.busy_ns(reduced, c) / 1e9 for c in reduced.chips]
        extra = {"busy_s": sum(busy) / len(busy),
                 "window_s": reduced.window_ns / 1e9}
        result["breakdown"] = tracing.breakdown(reduced)
    else:
        steps = phases * max(1, math.ceil(seconds / step_s / phases))
        job.stamps = []
        setup_s = time.perf_counter() - T0
        with GcWatch() as gcw:
            t = time.perf_counter()
            job.train(steps)
            job.block()
            end = time.perf_counter()
        window_s = end - t
        log(host_report(job.stamps, end, job.job["log_every"], gcw))
        tps = steps * tokens_per_step / window_s
        values = {
            "tokens_per_s": tps,
            "mfu": 100.0 * tps * flops / (len(devices) * peak["bf16_flops"]),
            "setup_s": setup_s,
        }
        for m in cell["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        log(f"[window] {steps} steps in {window_s:.6f} s")
    again = job.compiles() - compiled
    log(f"[compile] phase executables compiled inside the window: {again}")
    if again:
        raise RuntimeError(f"{again} phase executable(s) compiled in the window")
    window_losses = [h["total_loss"] for h in job.tr.history[phases + 1:]]
    failed = sum(not math.isfinite(x) for x in window_losses)
    mem = job.memory_peak()
    log(f"[memory] peak_bytes_in_use {mem}")
    host_batches = job.host_ring[:compared]
    del job
    gc.collect()

    t = time.perf_counter()
    train = dict(cell["traffic"]["optimizer"],
                 interval=cell["traffic"]["interval"])
    ref = reference.run(cell["config"]["config"], train, seed, host_batches,
                        steps=compared)
    log(f"[reference] {compared} steps in {time.perf_counter() - t:.3f} s")
    nums = correctness.numbers(prog, ref)
    ok, checks = correctness.judge(nums, cell["limits"])
    result.update({
        "correct": ok, "attempted": steps, "failed": failed,
        "metrics": metrics,
        "device": device_info(devices, memory_peak_bytes=mem, **extra),
        "checks": checks,
    })
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)
    devices = tpu_devices(cell["chips"])
    from bench.peaks import peaks

    peak = peaks(devices[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peak)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
