"""Operations and bytes that the benchmark counts, from shapes alone.

Model FLOPs follow PaLM (Chowdhery et al. 2022, appendix B): a training
token costs ``6 N + 12 L H Q T``, where ``N`` counts the parameters of the
matrix multiplications (the LM head over the configuration's real
vocabulary, not its padding; no embedding gather, norm gain or bias), ``L``
layers of ``H`` heads of size ``Q`` attend over the full causal length
``T``, and recomputation is not counted.
"""
from __future__ import annotations

EF_UPDATE_OPERANDS = 4      # g and r in, send and r' out
VOCAB_MULTIPLE = 128        # the embedding and head rows are padded to it


def matmul_params(arch: dict) -> int:
    d, f = arch["d_model"], arch["d_ff"]
    H, K, Q = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    per_layer = d * H * Q * 2 + d * K * Q * 2 + 3 * d * f
    return arch["num_layers"] * per_layer + d * arch["vocab_size"]


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    attn = 12 * arch["num_layers"] * arch["num_heads"] * arch["head_dim"]
    return 6.0 * matmul_params(arch) + attn * seq_len


def param_elements(arch: dict) -> int:
    """Elements of the parameter tree as the decoder holds it: the
    embedding and the untied head over the padded vocabulary, norm gains,
    and the QKV biases where the configuration has them.  Each is one
    gradient element that the error-feedback kernel streams every step."""
    d, f, L = arch["d_model"], arch["d_ff"], arch["num_layers"]
    H, K, Q = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    vocab = -(-arch["vocab_size"] // VOCAB_MULTIPLE) * VOCAB_MULTIPLE
    per_layer = d * H * Q * 2 + d * K * Q * 2 + 3 * d * f + 2 * d
    if arch["qkv_bias"]:
        per_layer += H * Q + 2 * K * Q
    return L * per_layer + 2 * vocab * d + d


def ef_update_bytes(elements: int, itemsize: int = 4) -> int:
    """Bytes the fused error-feedback kernel must move for ``elements``."""
    return EF_UPDATE_OPERANDS * itemsize * elements
