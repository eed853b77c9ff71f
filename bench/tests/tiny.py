"""A tiny training cell for the CPU tests: the harness's whole path at a
size a test run can hold."""
import os

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

ARCH = {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 32, "d_ff": 256, "vocab_size": 500, "qkv_bias": True,
        "mlp_act": "swiglu", "rope_theta": 10000.0, "norm_eps": 1e-6,
        "param_dtype": "float32", "compute_dtype": "bfloat16",
        "remat": True, "tie_embeddings": True, "attn_chunk": 32,
        "xent_chunk": 32}
TRAFFIC = {"kind": "train", "chips": 1, "seq_len": 64, "global_batch": 8,
           "compressor": "covap", "interval": 4,
           "optimizer": {"name": "adamw", "lr": 1.5e-4, "b1": 0.9,
                         "b2": 0.999, "eps": 1e-8, "warmup_steps": 1,
                         "total_steps": 100000},
           "log_every": 10, "ring_batches": 8,
           "corpus": {"branching": 4, "explore": 0.05}}
# the tiny cell's own limits, set as the cells' are (bench/limits/): above
# what the program read on the CPU (loss 1.2e-4, worst leaf 2.5e-3, median
# leaf 9e-4, change 7.7e-4, residual 3.4e-3 over three seeds) and below
# what the float8 control (median leaf 7e-3 and more) and the faults read
# there (no feedback: residual 8.4e-2 and more)
LIMITS = {"loss_gap": 4e-4, "grad1_gap": 3e-2, "grad1_median_gap": 3e-3,
          "change_gap": 4e-3, "resid_gap": 2e-2}


def cell(chips: int = 1, **arch) -> dict:
    return {
        "name": "tiny", "chips": chips,
        "config": {"arch": "qwen1.5-0.5b", "reference": "decoder",
                   "config": dict(ARCH, **arch)},
        "traffic": dict(TRAFFIC, chips=chips),
        "limits": LIMITS,
        "reference": "bench.references.decoder",
        "end_to_end": [
            {"name": "tokens_per_s", "unit": "tokens/s"},
            {"name": "mfu", "unit": "%"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }


def devices(n: int = 1):
    return jax.devices()[:n]


PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
