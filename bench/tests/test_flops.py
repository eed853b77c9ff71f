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


# DeepSeek-V2-Lite (hf:deepseek-ai/DeepSeek-V2-Lite) cut to one chip's share:
# 1 dense and 4 MoE layers, 8 of the 64 routed experts, 1/8 of the vocabulary
DSV2_LITE_CUT = {
    "num_layers": 5, "dense_layers": 1, "d_model": 2048, "num_heads": 16,
    "num_kv_heads": 16, "head_dim": 128, "d_ff": 1408, "dense_d_ff": 10944,
    "vocab_size": 12800, "qkv_bias": False, "num_experts": 8,
    "router_experts": 64, "experts_per_token": 6, "num_shared_experts": 2,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "q_lora_rank": 0,
}


def test_deepseek_v2_lite_cut_matmul_params():
    # latent attention a layer: wq 2048 x 16 x (128 + 64) = 6,291,456;
    # wkv_a 2048 x (512 + 64) = 1,179,648; wkv_b 512 x 16 x (128 + 128)
    # = 2,097,152; wo 16 x 128 x 2048 = 4,194,304; together 13,762,560
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert attn == 13_762_560
    # the dense layer: attention + SwiGLU 3 x 2048 x 10944 = 67,239,936
    dense = attn + 67_239_936
    assert dense == 81_002_496
    # an MoE layer: attention; router 2048 x 64 = 131,072; shared MLP
    # 3 x 2048 x (2 x 1408) = 17,301,504; of one expert's 3 x 2048 x 1408
    # = 8,650,752, k e / E = 6 x 8 / 64 = 0.75 experts a token = 6,488,064
    moe = attn + 131_072 + 17_301_504 + 6_488_064
    assert moe == 37_683_200
    # the head over the vocabulary share 2048 x 12800 = 26,214,400
    n = dense + 4 * moe + 26_214_400
    assert n == 257_949_696
    assert flops.matmul_params(DSV2_LITE_CUT) == n


def test_deepseek_v2_lite_cut_flops_per_token():
    # attention core 6 x 5 layers x 16 heads x (192 + 128) x 4096
    attn = 6 * 5 * 16 * (192 + 128) * 4096
    assert attn == 629_145_600
    assert flops.attention_flops_per_token(DSV2_LITE_CUT, 4096) == attn
    # routed experts: 6 x 4 MoE layers x 6,488,064 = 155,713,536
    routed = 6 * 4 * 6_488_064
    assert flops.routed_expert_flops_per_token(DSV2_LITE_CUT) == routed
    # 6 N + attention = 6 x 257,949,696 + 629,145,600
    want = 6 * 257_949_696 + attn
    assert want == 2_176_843_776
    assert flops.train_flops_per_token(DSV2_LITE_CUT, 4096) == want


def test_deepseek_v2_lite_cut_param_elements():
    # the dense layer: its matmuls 81,002,496, two norm gains 2 x 2048 and
    # the kv latent's norm gain 512
    dense = 81_002_496 + 2 * 2048 + 512
    assert dense == 81_007_104
    # an MoE layer: attention 13,762,560 + norms 4,096 + kv norm 512; router
    # 131,072; shared MLP 17,301,504; all 8 held experts 8 x 8,650,752
    moe = 13_762_560 + 4096 + 512 + 131_072 + 17_301_504 + 8 * 8_650_752
    assert moe == 100_405_760
    # embedding and head 2 x 12800 x 2048 (12800 is a multiple of 128);
    # final norm 2048
    want = dense + 4 * moe + 52_428_800 + 2048
    assert want == 535_060_992
    assert flops.param_elements(DSV2_LITE_CUT) == want


# deepseek-moe-16b's small preset (src/repro/configs/deepseek_moe_16b.py):
# every layer MoE, standard attention, the router as wide as the experts held
SMALL_MOE = {
    "num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 4,
    "head_dim": 32, "d_ff": 96, "vocab_size": 512, "qkv_bias": False,
    "num_experts": 4, "num_shared_experts": 1, "experts_per_token": 2,
}


def test_moe_without_latent_attention_or_dense_layers():
    # attention a layer 4 x 128 x 128 = 65,536; router 128 x 4 = 512;
    # shared MLP 3 x 128 x 96 = 36,864; one expert 3 x 128 x 96 = 36,864,
    # and with the router over the 4 held, k e / E = 2 x 4 / 4 = 2 a token
    layer = 65_536 + 512 + 36_864 + 2 * 36_864
    # head 128 x 512 = 65,536
    n = 2 * layer + 65_536
    assert n == 418_816
    assert flops.matmul_params(SMALL_MOE) == n
    assert flops.routed_expert_flops_per_token(SMALL_MOE) == 6 * 2 * 73_728
    # attention 6 x 2 x 4 x (32 + 32) x 64 = 196,608
    assert flops.train_flops_per_token(SMALL_MOE, 64) == 6 * n + 196_608
    # elements: attention and norms 65,536 + 256; router, shared MLP and
    # 4 experts 512 + 36,864 + 4 x 36,864; embedding and head 2 x 512 x 128;
    # final norm 128
    want = 2 * (65_792 + 512 + 36_864 + 147_456) + 131_072 + 128
    assert want == 632_448
    assert flops.param_elements(SMALL_MOE) == want


@pytest.mark.parametrize("key, value", [
    ("ssm_state", 64), ("attn_every", 6), ("slstm_every", 2),
    ("is_encdec", True), ("sliding_window", 4096), ("local_global", True),
    ("q_lora_rank", 1536), ("modality", "vision"),
])
def test_what_cannot_be_counted_is_refused(key, value):
    cut = dict(DSV2_LITE_CUT, **{key: value})
    for count in (flops.matmul_params, flops.param_elements,
                  flops.routed_expert_flops_per_token):
        with pytest.raises(ValueError, match=key):
            count(cut)
    for count in (flops.train_flops_per_token,
                  flops.attention_flops_per_token):
        with pytest.raises(ValueError, match=key):
            count(cut, 4096)


def _program_tree_elements(arch_name: str, arch: dict) -> int:
    """Elements of the program's own parameter tree, as ``Trainer._shapes``
    holds it: ``jax.eval_shape`` of the model's init."""
    import math

    import jax

    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config(arch_name).with_(**arch))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes))


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH_CONFIGS = [c["name"] for c in json.load(_f)["configs"]]


@pytest.mark.parametrize("name", BENCH_CONFIGS)
def test_param_elements_are_the_program_tree(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        config = json.load(f)
    got = _program_tree_elements(config["arch"], config["config"])
    assert got == flops.param_elements(config["config"])


def test_param_elements_are_the_program_moe_tree():
    got = _program_tree_elements("deepseek-moe-16b", SMALL_MOE)
    assert got == flops.param_elements(SMALL_MOE) == 632_448
