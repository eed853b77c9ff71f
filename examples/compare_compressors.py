"""Table-VII-style comparison: train the same model with every GC scheme and
report wall time + final loss (the paper's time-to-solution experiment at
laptop scale).

    PYTHONPATH=src python examples/compare_compressors.py [--steps 30]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.data import DataConfig, make_loader
from repro.models import build_model
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer, copy_train_state

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=30)
ap.add_argument("--schemes", default="none,covap,fp16,topk,randomk,efsignsgd,powersgd,fp8wire")
args = ap.parse_args()

cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
model = build_model(cfg)
data = DataConfig(vocab_size=cfg.vocab_size, seq_len=48, global_batch=8)

print(f"{'scheme':12s} {'wall_s':>8s} {'final_loss':>11s} {'sent_ratio':>10s}")
for scheme in args.schemes.split(","):
    tc = TrainConfig(compressor=scheme, interval=4, bucket_bytes=1 << 14,
                     max_buckets=32, log_every=10**9)
    tr = Trainer(model, adamw(3e-3), tc)
    state = tr.init_state(jax.random.PRNGKey(0))
    loader = iter(make_loader(data))
    # warm-up/compile every phase executable outside the timed region
    batch = next(loader)
    for ph in range(tr.num_phases):
        # the step consumes (donates) its state: warm up on a copy
        warm_state = copy_train_state(state)
        tr._phase_fn(ph)(warm_state["params"], warm_state["opt"],
                         warm_state["comp"], batch, jnp.int32(ph))
    t0 = time.perf_counter()
    losses = []
    for i in range(args.steps):
        batch = next(loader)
        phase = state["step"] % tr.num_phases
        p, o, c, m = tr._phase_fn(phase)(
            state["params"], state["opt"], state["comp"], batch,
            jnp.int32(state["step"]))
        state = {"params": p, "opt": o, "comp": c, "step": state["step"] + 1}
        losses.append(float(m["loss"]))
    wall = time.perf_counter() - t0
    # volume ratio from the compressor's static accounting
    from repro.core import get_compressor
    comp = tr.compressor
    _, _, stats = comp.sync(
        jax.tree.map(jnp.zeros_like, state["params"]),
        comp.init_state(state["params"], tr.plan),
        plan=tr.plan, phase=0, step=0, axis_names=())
    print(f"{scheme:12s} {wall:8.2f} {losses[-1]:11.4f} "
          f"{stats.volume_ratio:9.1f}x")
