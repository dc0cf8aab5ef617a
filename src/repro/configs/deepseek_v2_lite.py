"""DeepSeek-V2-Lite: latent attention (MLA) with YaRN rotary, one leading
dense layer, then 26 layers of 64 routed experts (top-6, softmax, no
renormalisation) beside 2 shared ones.  [arXiv:2405.04434; hf
deepseek-ai/DeepSeek-V2-Lite config.json]

Published, uncut sizes.  Its expert layers route dropless through a
dynamic loop over the pairs routed here, which serving runs and reverse
mode cannot differentiate, so it is a serving preset and is not in the
training registry (``repro.configs.REGISTRY``)."""
from .base import ArchConfig, MLAConfig, MoEConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_head=192, d_ff=1408, vocab_size=102400, activation="swiglu",
    rope_theta=10000.0, norm_eps=1e-6,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YarnScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    first_k_dense=1, d_ff_dense=10944,
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  norm_topk_prob=False),
)
