"""deepseek-v2-236b [moe] — MLA attention + 160-expert MoE.

The published ``config.json`` (hf:deepseek-ai/DeepSeek-V2; arXiv:2405.04434):
60 layers, d_model 5120, 128 heads, vocab 102400 (untied), RMSNorm eps 1e-6.
Layer 0 has a dense 12288-wide MLP (``first_k_dense_replace`` 1); the other
59 a MoE of 160 routed experts of width 1536 plus 2 shared ones, 6 experts a
token, routed ``group_limited_greedy``: 8 groups, each scored by its best
expert, the top 3 groups eligible; softmax scores, not renormalised
(``norm_topk_prob`` false), scaled by ``routed_scaling_factor`` 16.
MLA: q_lora 1536, kv_lora 512, qk_nope 128 + qk_rope 64 per head,
v_head_dim 128 — the compressed 576-wide KV cache.  Rope: YaRN, factor 40
over 4096 original positions, beta_fast 32, beta_slow 1, mscale and
mscale_all_dim 0.707.
"""

from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,        # MLA: per-head K/V expanded from the latent
    head_dim=128,
    d_ff=1536,             # moe_intermediate_size
    vocab=102400,
    block_pattern=("mla",),
    mlp_pattern=("moe",),
    first_layer_dense=True,
    d_ff_dense=12288,
    attn_kind="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=160,
    n_shared_experts=2,
    top_k=6,
    d_ff_expert=1536,
    n_group=8,
    topk_group=3,
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    rope_theta=1e4,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm="rmsnorm",
    norm_eps=1e-6,
    act="silu",
)
