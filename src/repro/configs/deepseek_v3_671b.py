"""DeepSeek-V3-671B [arXiv:2412.19437; hf:deepseek-ai/DeepSeek-V3 config.json].

61 layers, d_model 7168, RMSNorm eps 1e-6, untied 129,280-row head, MTP.
MLA (128 heads): q_lora 1536, kv_lora 512, rope 64 + nope 128, v 128, with
RMSNorms on both latents; rope on interleaved pairs with YaRN (θ 1e4,
factor 40, β_fast 32, β_slow 1, original 4,096, mscale and mscale_all_dim 1:
equal, so cos and sin keep their scale, and the softmax scale is
192^-0.5 × (0.1 ln 40 + 1)²).  The first 3 layers
are dense (d_ff 18432); the rest are MoE: 1 shared + 256 routed experts of
width 2048, noaux_tc routing: sigmoid scores, a bias per expert for the
choice, 8 groups of which 4 are searched, top 8, weights renormalised and
× 2.5.
"""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,           # MLA: kv "heads" = q heads, latent-compressed
    d_ff=18432,               # dense-layer FFN width
    vocab=129280,
    norm_eps=1e-6,
    attn_kind="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_original_len=4096,
    yarn_mscale_all_dim=1.0,
    n_experts=256,
    n_shared_experts=1,
    top_k=8,
    d_ff_expert=2048,
    n_dense_layers=3,
    moe_score="sigmoid",
    moe_groups=8,
    moe_groups_kept=4,
    moe_scale=2.5,
    mtp=True,
    rope_theta=1e4,
))
