"""deepseek-v2-lite-16b [moe]: the published DeepSeek-V2-Lite
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json;
arXiv:2405.04434).

27 layers at hidden size 2048; the first (``first_k_dense_replace`` 1) is
dense with d_ff 10944, the other 26 are MoE: 64 routed experts of width
1408, top-6 by softmax, greedy, ``norm_topk_prob`` false (the raw
probabilities, as ``routed_scaling_factor`` is 1), plus 2 shared experts.
MLA with 16 heads, ``kv_lora_rank`` 512 (RMS-normalised latent), rope 64,
nope 128, v 128, no query compression.  Vocabulary 102400,
``rms_norm_eps`` 1e-6, ``rope_theta`` 10000 with YaRN scaling (factor 40,
original 4096 positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim
= 0.707).  15.71B parameters.
"""

from repro.configs.registry import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,  # MLA: kv heads = q heads after decompression
    d_ff=10944,  # dense-layer FFN width (layer 0)
    vocab_size=102400,
    head_dim=192,  # qk_nope (128) + qk_rope (64)
    attn_type="mla",
    rope_theta=10000.0,
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    qk_nope_head_dim=128,
    v_head_dim=128,
    rope_scaling_factor=40.0,
    rope_original_max_position=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    mlp_type="swiglu",
    norm_eps=1e-6,
    cache_family="mla",  # paged decode over shared-latent block pools
    notes="DeepSeek-V2-Lite: MLA attention + fine-grained MoE.",
)
