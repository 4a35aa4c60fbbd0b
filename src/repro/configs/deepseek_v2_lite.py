"""DeepSeek-V2-Lite: latent attention (MLA) and DeepSeekMoE.

[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite] — 27L d_model=2048 16H,
MLA with a 512-wide latent KV (k: 128 nope + 64 shared rotary, v: 128, no q
LoRA), YaRN rope x40; layer 0 dense (d_ff 10944), layers 1-26 MoE with 64
routed experts of width 1408, top-6 softmax gates left unnormalised, and 2
shared experts; vocab 102400, untied.  15,706,484,224 parameters with norms.
"""
from repro.configs.base import ArchConfig, MoEConfig, YarnRope

CONFIG = ArchConfig(
    arch_id="deepseek_v2_lite",
    family="moe",
    source="arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10_944,
    vocab=102_400,
    attn_kind="mla",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    mlp_act="silu",
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  n_shared_experts=2, norm_topk=False),
    n_dense_layers=1,
    rope_theta=10_000.0,
    rope_yarn=YarnRope(factor=40.0, original_max_position=4096,
                       beta_fast=32.0, beta_slow=1.0, mscale=0.707),
    tie_embeddings=False,
    norm_eps=1e-6,
)
