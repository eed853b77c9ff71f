"""Operations and bytes that the benchmark counts, from shapes alone.

Model FLOPs follow PaLM (Chowdhery et al. 2022, appendix B): a training
token costs ``6 N + 6 L H (Qk + Qv) T``, where ``N`` counts the parameters
of the matrix multiplications that the token passes through (the LM head
over the configuration's real vocabulary, not its padding; no embedding
gather, norm gain or bias), ``L`` layers of ``H`` heads, with query-key
size ``Qk`` and value size ``Qv``, attend over the full causal length
``T``, and recomputation is not counted.  Where ``Qk == Qv == Q`` this is
PaLM's ``12 L H Q T``.

The configuration is the ``config`` dict of ``bench/configs/<name>.json``.
Besides the dense keys (``num_layers``, ``d_model``, ``num_heads``,
``num_kv_heads``, ``head_dim``, ``d_ff``, ``vocab_size``, ``qkv_bias``)
these are read, each with a default that leaves a dense configuration as
it is:

* ``num_experts`` (0): the routed experts held on this chip; above 0 the
  layers after the leading dense ones are sparse-expert (MoE) layers;
* ``router_experts`` (``num_experts``): the published routed count, the
  router's width;
* ``experts_per_token``: ``k``, read where ``num_experts`` is above 0;
* ``num_shared_experts`` (0): shared experts, held as one MLP of width
  ``num_shared_experts * d_ff``;
* ``d_ff``: the width of each expert in an MoE configuration;
* ``dense_layers`` (0): the leading dense layers, of width ``dense_d_ff``;
* ``kv_lora_rank`` (0): above 0 the attention is latent (MLA) with the
  latent rank ``r``, no query compression, and the head sizes
  ``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim``.

A chip holds ``e`` of the router's ``E`` experts; the others lie on further
chips.  Its parameters and gradients count the ``e`` held experts.  Its
FLOPs count ``k e / E`` experts a token: what this chip's program computes
for the tokens it sees, under the configuration rather than the router's
draw.  A configuration with a part this module cannot count (a state-space
or recurrent block, an encoder, windowed attention, query compression, a
modality other than text) is refused with ``ValueError``.
"""
from __future__ import annotations

EF_UPDATE_OPERANDS = 4      # g and r in, send and r' out
VOCAB_MULTIPLE = 128        # the embedding and head rows are padded to it

# keys whose blocks are not counted, with the value that leaves them out
UNCOUNTED = {"ssm_state": 0, "attn_every": 0, "slstm_every": 0,
             "is_encdec": False, "sliding_window": 0, "local_global": False,
             "q_lora_rank": 0, "modality": "text"}


def _countable(arch: dict) -> None:
    for key, plain in UNCOUNTED.items():
        value = arch.get(key, plain)
        if value != plain and value is not None:
            raise ValueError(f"bench/flops.py cannot count {key}={value!r}")
    if arch.get("kv_lora_rank", 0) > 0 and arch.get("qkv_bias", False):
        raise ValueError("bench/flops.py cannot count qkv_bias with latent "
                         "attention (kv_lora_rank > 0)")


def _exact(num: int, den: int):
    """``num / den``, as an integer where it is one."""
    return num // den if num % den == 0 else num / den


def _head_sizes(arch: dict) -> tuple[int, int]:
    """(query-key size, value size) of one head."""
    if arch.get("kv_lora_rank", 0) > 0:
        return (arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"],
                arch["v_head_dim"])
    return arch["head_dim"], arch["head_dim"]


def _attention_params(arch: dict) -> int:
    """One layer's attention projections."""
    d, H = arch["d_model"], arch["num_heads"]
    r = arch.get("kv_lora_rank", 0)
    if r > 0:
        nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
        v = arch["v_head_dim"]
        # wq, wkv_a, wkv_b, wo
        return (d * H * (nope + rope) + d * (r + rope)
                + r * H * (nope + v) + H * v * d)
    K, Q = arch["num_kv_heads"], arch["head_dim"]
    return d * H * Q * 2 + d * K * Q * 2


def _layers(arch: dict) -> tuple[int, int, int]:
    """(leading dense layers, further dense layers, MoE layers)."""
    L, lead = arch["num_layers"], arch.get("dense_layers", 0)
    if arch.get("num_experts", 0) > 0:
        return lead, 0, L - lead
    return lead, L - lead, 0


def _moe_shared_params(arch: dict) -> int:
    """An MoE layer's matmuls outside the routed experts: the router and
    the shared MLP."""
    d, f = arch["d_model"], arch["d_ff"]
    E = arch.get("router_experts", arch["num_experts"])
    return d * E + 3 * d * f * arch.get("num_shared_experts", 0)


def _dense_mlp_params(arch: dict) -> int:
    """The MLPs of the dense layers: the leading ones at ``dense_d_ff``,
    the further ones at ``d_ff``."""
    lead, dense, _ = _layers(arch)
    d = arch["d_model"]
    return ((lead * 3 * d * arch["dense_d_ff"] if lead else 0)
            + dense * 3 * d * arch["d_ff"])


def _dense_matmul_params(arch: dict) -> int:
    """Every matmul parameter a token passes through but the routed
    experts'."""
    _, _, moe = _layers(arch)
    return (arch["num_layers"] * _attention_params(arch)
            + _dense_mlp_params(arch)
            + (moe * _moe_shared_params(arch) if moe else 0)
            + arch["d_model"] * arch["vocab_size"])


def _routed_params_per_token(arch: dict):
    """The routed experts' matmul parameters a token passes through on this
    chip: ``k e / E`` experts in each MoE layer."""
    _, _, moe = _layers(arch)
    if not moe:
        return 0
    e = arch["num_experts"]
    E = arch.get("router_experts", e)
    k = arch["experts_per_token"]
    return _exact(moe * k * e * 3 * arch["d_model"] * arch["d_ff"], E)


def matmul_params(arch: dict):
    """``N``: the matmul parameters a token passes through on this chip."""
    _countable(arch)
    return _dense_matmul_params(arch) + _routed_params_per_token(arch)


def attention_flops_per_token(arch: dict, seq_len: int) -> int:
    """The attention core's FLOPs a training token: ``6 L H (Qk + Qv) T``."""
    _countable(arch)
    qk, qv = _head_sizes(arch)
    return 6 * arch["num_layers"] * arch["num_heads"] * (qk + qv) * seq_len


def routed_expert_flops_per_token(arch: dict):
    """The routed experts' FLOPs a training token on this chip; 0 for a
    dense configuration."""
    _countable(arch)
    return 6 * _routed_params_per_token(arch)


def train_flops_per_token(arch: dict, seq_len: int):
    _countable(arch)
    return (6 * _dense_matmul_params(arch)
            + routed_expert_flops_per_token(arch)
            + attention_flops_per_token(arch, seq_len))


def param_elements(arch: dict) -> int:
    """Elements of the parameter tree as the decoder holds it: the
    embedding and the untied head over the padded vocabulary, norm gains,
    the latent attention's kv norm gain, the QKV biases where the
    configuration has them, and in an MoE layer the router, the shared MLP
    and the ``num_experts`` experts held.  Each is one gradient element
    that the error-feedback kernel streams every step."""
    _countable(arch)
    _, _, moe = _layers(arch)
    d = arch["d_model"]
    attn = _attention_params(arch) + 2 * d + arch.get("kv_lora_rank", 0)
    if arch.get("qkv_bias", False):
        H, K, Q = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
        attn += H * Q + 2 * K * Q
    ffn = _dense_mlp_params(arch)
    if moe:
        ffn += moe * (_moe_shared_params(arch)
                      + arch["num_experts"] * 3 * d * arch["d_ff"])
    vocab = -(-arch["vocab_size"] // VOCAB_MULTIPLE) * VOCAB_MULTIPLE
    return arch["num_layers"] * attn + ffn + 2 * vocab * d + d


def ef_update_bytes(elements: int, itemsize: int = 4) -> int:
    """Bytes the fused error-feedback kernel must move for ``elements``."""
    return EF_UPDATE_OPERANDS * itemsize * elements
