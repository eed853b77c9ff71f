"""bench/flops.py against arithmetic done by hand."""
import json
import os

import pytest

from bench import flops
from bench.tests.tiny import ROOT


def arch(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)["config"]


def test_gpt2_paper_flops_per_token():
    # per layer: q, k, v, o 4 x 768 x 768 = 2,359,296; gated MLP
    # 3 x 768 x 3072 = 7,077,888; 12 layers = 113,246,208; head over the
    # real vocabulary 768 x 50257 = 38,597,376
    n = 113_246_208 + 38_597_376
    assert flops.matmul_params(arch("gpt2-paper")) == n
    attn = 12 * 12 * 12 * 64 * 1024          # 113,246,208
    want = 6 * n + attn                      # 1,024,305,408
    assert flops.train_flops_per_token(arch("gpt2-paper"), 1024) == want
    assert round(want / 1e9, 3) == 1.024


def test_qwen_cut_flops_per_token():
    # per layer: 4 x 1024 x 1024 = 4,194,304; 3 x 1024 x 2816 = 8,650,752;
    # 16 layers = 205,520,896; head 1024 x 18992 = 19,447,808
    n = 205_520_896 + 19_447_808
    assert flops.matmul_params(arch("qwen1.5-0.5b")) == n
    want = 6 * n + 12 * 16 * 16 * 64 * 1024  # 1,551,114,240
    assert flops.train_flops_per_token(arch("qwen1.5-0.5b"), 1024) == want
    assert round(want / 1e9, 3) == 1.551


@pytest.mark.parametrize("n", [1, 6_553_344, 190_532_352])
def test_ef_update_moves_16_bytes_per_element(n):
    # g and r read, send and r' written, 4 bytes each
    assert flops.ef_update_bytes(n) == 16 * n


@pytest.mark.parametrize("name, want", [
    # 12 x (4 x 768^2 + 3 x 768 x 3072 + 2 x 768) = 113,264,640; embedding
    # and head over the padded 50304 rows 2 x 50304 x 768 = 77,266,944;
    # final norm 768
    ("gpt2-paper", 113_264_640 + 77_266_944 + 768),
    # 16 x (4 x 1024^2 + 3 x 1024 x 2816 + 2 x 1024 + 3 x 1024 biases)
    # = 205,602,816; 2 x 19072 x 1024 = 39,059,456; final norm 1024
    ("qwen1.5-0.5b", 205_602_816 + 39_059_456 + 1024),
])
def test_param_elements_count_what_the_decoder_holds(name, want):
    assert flops.param_elements(arch(name)) == want
