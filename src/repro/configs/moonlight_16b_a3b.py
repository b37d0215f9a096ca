"""moonlight-16b-a3b [moe] — hf:moonshotai/Moonlight-16B-A3B (model_type
deepseek_v3).

27 layers, d_model 2048, 16 heads of multi-head latent attention
(q_lora_rank null, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128,
kv_a_layernorm; softmax scale 192^-0.5), vocabulary 163840 untied,
rope_theta 50000, RMS eps 1e-5.  The first layer is dense (ff 11264);
the other 26 hold 64 routed experts (ff 1408) and 2 shared ones (one
MLP of 2816), routed by sigmoid scores with DeepSeek-V3's noaux_tc
rule: `e_score_correction_bias` picks the top 6, the unbiased scores of
those weigh them, normalised to sum 1 and scaled by 2.446 (n_group =
topk_group = 1).
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv=16,
    d_ff=11264,             # the dense first layer's ff
    d_ff_expert=1408, n_experts=64, top_k=6, n_shared=2,
    first_dense_layers=1,
    vocab=163840, act="silu_glu", rope_theta=5e4, norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    router_scoring="sigmoid", routed_scaling=2.446,
)

# the same block at a small size: latent attention, sigmoid routing, a
# dense first layer, and a share of the experts (4 of the router's 8)
SMOKE = ModelConfig(
    name="moonlight-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv=4,
    d_ff=192, d_ff_expert=32, n_experts=8, top_k=2, n_shared=1,
    first_dense_layers=1, vocab=512, act="silu_glu", rope_theta=5e4,
    norm_eps=1e-5,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16,
    router_scoring="sigmoid", routed_scaling=2.446,
    experts_held=(4, 8),
)
