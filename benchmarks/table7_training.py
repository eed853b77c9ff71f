"""Paper Table VII / Fig 6: time-to-solution and accuracy per GC scheme.

Real CPU training runs (reduced GPT-2, learnable Markov data): wall time for
N steps + final loss per compressor.  The paper's qualitative result to
reproduce: COVAP/FP16 match the DDP baseline loss while sparsifiers with
aggressive ratios lag at equal step count.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.data import DataConfig, make_loader
from repro.models import build_model
from repro.optim import adamw
from repro.train.trainer import TrainConfig, Trainer, copy_train_state

from .common import row

SCHEMES = [
    ("none", {}),          # DDPovlp baseline
    ("covap", {}),
    ("fp16", {}),
    ("fp8wire", {}),
    ("topk", {"ratio": 0.01}),
    ("dgc", {"ratio": 0.001}),
    ("randomk", {"ratio": 0.01}),
    ("efsignsgd", {}),
    ("powersgd", {"rank": 2}),
]

STEPS = 25


def run():
    cfg = get_reduced("gpt2-paper").with_(vocab_size=256)
    model = build_model(cfg)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=48, global_batch=8)
    rows = []
    for name, opts in SCHEMES:
        tc = TrainConfig(compressor=name, compressor_options=opts, interval=4,
                         bucket_bytes=1 << 14, max_buckets=32,
                         log_every=10 ** 9)
        tr = Trainer(model, adamw(3e-3), tc)
        state = tr.init_state(jax.random.PRNGKey(0))
        loader = iter(make_loader(data))
        # compile all phases first
        warm = next(loader)
        for ph in range(tr.num_phases):
            # the step consumes (donates) its state: warm up on a copy
            warm_state = copy_train_state(state)
            tr._phase_fn(ph)(warm_state["params"], warm_state["opt"],
                             warm_state["comp"], warm, jnp.int32(ph))
        t0 = time.perf_counter()
        losses = []
        for _ in range(STEPS):
            batch = next(loader)
            phase = state["step"] % tr.num_phases
            p, o, c, m = tr._phase_fn(phase)(
                state["params"], state["opt"], state["comp"], batch,
                jnp.int32(state["step"]))
            state = {"params": p, "opt": o, "comp": c,
                     "step": state["step"] + 1}
            losses.append(float(m["loss"]))
        wall = time.perf_counter() - t0
        rows.append(row(
            f"table7/{name}", wall / STEPS,
            f"final_loss={losses[-1]:.4f};steps={STEPS}",
        ))
    return rows
