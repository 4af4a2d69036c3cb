"""moonlight-16b-a3b [moe] — 27L d2048, DeepSeek-V3 block: latent
attention (MLA: 16 heads, kv_lora_rank 512, q/k 128 + 64 rope, v 128,
no q_lora), one leading dense layer (d_ff 11264), then 26 MoE layers of
64 routed experts (width 1408, top-6, sigmoid scores chosen with a
correction bias, normalised, scaled by 2.446) plus 2 shared experts;
vocab 163840, untied head, rope 50000, no embedding scale.
[hf:moonshotai/Moonlight-16B-A3B config.json]

Departure: rope is the program's half-split form, not DeepSeek's
interleaved one — a fixed permutation of the rope columns of ``wq`` and
``wkv_a``, the same model under random weights.
"""
from repro.models import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, first_dense=1, d_model=2048, n_heads=16, n_kv=16,
    head_dim=192, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128, d_ff=11264, vocab=163840,
    pattern=("moe",), n_experts=64, top_k=6, d_expert=1408, d_shared=2816,
    router="sigmoid", routed_scale=2.446, act="silu",
    tie_embeddings=False, embed_scale=False, rope_theta=50_000.0,
    norm_eps=1e-5,
)

REDUCED = CONFIG.replace(
    n_layers=4, d_model=64, n_heads=4, n_kv=4, head_dim=24,
    kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
    d_ff=128, vocab=512, n_experts=16, top_k=3, d_expert=32, d_shared=64,
    dtype="float32", remat=False)
