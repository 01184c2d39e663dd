"""deepseek-v2-lite — latent attention (MLA) + fine-grained MoE
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite]. 27 layers, d 2048:
layer 0 a dense SwiGLU of 10,944, layers 1-26 64 routed experts of 1,408
(top-6, softmax gates not renormalized, routed scaling 1) plus 2 shared; every layer MLA
with 16 heads, no query LoRA, a 512-lane latent and a 64-lane rope key,
YaRN rope (factor 40 over 4,096 positions) on interleaved pairs.
"""

from repro.configs.base import ModelConfig
from repro.models.common import YarnRope
from repro.models.moe import MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,                      # q/k: 128 nope + 64 rope
    d_ff=10944,                        # the dense layer 0
    vocab_size=102400,
    prefix_pattern=("mla+mlp",),
    block_pattern=("mla+moe",),
    rope_theta=10000.0,
    norm_eps=1e-6,
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  capacity_factor=1.25, norm_topk_prob=False),
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_yarn=YarnRope(factor=40.0, original_max_position=4096,
                       beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                       mscale_all_dim=0.707),
    rope_interleave=True,
)

SMOKE = CONFIG.scaled(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24, d_ff=128,
    vocab_size=512, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
    moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=1,
                  capacity_factor=-1.0, norm_topk_prob=False),
    param_dtype="float32", activation_dtype="float32", remat="none",
    q_chunk=16,
)
