"""Model FLOPs of a train step of a DeepSeek-V3 stage (Moonlight), kept
with the benchmark.

Matrix products of the forward pass, times three for the forward and
backward passes; recomputed operations do not count, nor does the
embedding lookup.  Per token: the latent attention's projections
(``wq``, ``wkv_a``, ``wkv_b``, ``wo``) in every layer; the dense
layers' SwiGLU; in each MoE layer the router over all its outputs, the
shared experts, and the held experts at their expected share of the
pairs, ``num_experts_per_tok * n_routed_experts / router_outputs``
experts a token; the output head.  Per sequence, the causal attention
scores (q/k width ``qk_nope_head_dim + qk_rope_head_dim``) and values
(``v_head_dim``) over the ``T^2 / 2`` pairs.

``c`` is the configuration file's dict (HF key names).
"""
from __future__ import annotations


def attn_matmul_params(c: dict) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv)
            + H * dv * d)


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def moe_matmul_params(c: dict) -> float:
    """Router, shared experts, and the held experts a token reaches on
    average."""
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    held_share = (c["num_experts_per_tok"] * c["n_routed_experts"]
                  / c["router_outputs"])
    return (d * c["router_outputs"] + 3 * d * c["n_shared_experts"] * fe
            + held_share * 3 * d * fe)


def matmul_params_per_token(c: dict) -> float:
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    return (c["num_hidden_layers"] * attn_matmul_params(c)
            + n_dense * dense_ffn_params(c) + n_moe * moe_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def attn_flops_per_seq(c: dict, t: int) -> float:
    """Forward score and value products of one causal sequence."""
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (c["num_hidden_layers"] * 2.0 * (t * t / 2)
            * c["num_attention_heads"] * (qk + c["v_head_dim"]))


def train_step_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step over ``batch`` x ``seq``
    tokens."""
    return 3.0 * (2.0 * matmul_params_per_token(c) * batch * seq
                  + batch * attn_flops_per_seq(c, seq))
