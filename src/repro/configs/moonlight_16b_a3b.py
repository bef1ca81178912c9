"""moonlight-16b-a3b [moe]: 27L d_model=2048, latent attention (16 heads,
kv_lora_rank 512, qk 128 + 64 rope, v 128), layer 0 dense (SwiGLU 11264),
layers 1-26 with 64 routed experts of 1408 (6 a token, sigmoid noaux_tc
routing scaled 2.446) and 2 shared experts, vocab 163840, untied head
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, kv_heads=16, d_ff=1408,
    vocab=163840, rope_theta=50000.0,
    n_experts=64, top_k=6, score_func="sigmoid", norm_topk_prob=True,
    routed_scale=2.446, n_shared_experts=2, ep_size=1,
    n_dense_layers=1, dense_d_ff=11264,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    sparsity=0.85,
)

# same structure at CPU size: a dense layer, then expert layers whose chip
# holds 4 of the 16 routed experts (ep_size 4)
SMOKE = ArchConfig(
    name="moonlight-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, kv_heads=4, d_ff=32,
    vocab=512, rope_theta=50000.0,
    n_experts=16, top_k=4, score_func="sigmoid", norm_topk_prob=True,
    routed_scale=2.446, n_shared_experts=2, ep_size=4,
    n_dense_layers=1, dense_d_ff=96,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    sparsity=0.85, dtype="float32", remat=False,
)
