"""DeepSeek-V2-Lite — MLA without q compression (kv_lora=512), YaRN rope,
one dense layer then 26 MoE layers of 64 routed experts (top-6, weights not
renormalized) and 2 shared experts.  The MoE layer holds all 64 experts
here (``n_held=64``) and runs the dropless grouped path; a deployment's
expert-parallel share sets ``n_held``/``first_held`` (``MoEConfig``).
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]"""
from repro.configs.base import (LK, MLAConfig, MoEConfig, ModelConfig,
                                RopeScaling, Stage, register)

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,        # MLA: effectively MHA over the compressed cache
    head_dim=128,
    d_ff=10944,           # dense FF width of the first (non-MoE) layer
    vocab_size=102400,
    stages=(
        Stage((LK("mla", "mlp"),), repeats=1),
        Stage((LK("mla", "moe"),), repeats=26),
    ),
    act="swiglu",
    norm="rms",
    pos="rope",
    rope_theta=10_000.0,
    rope_scaling=RopeScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    moe=MoEConfig(n_experts=64, top_k=6, d_ff=1408, n_shared_experts=2,
                  norm_topk_prob=False, routed_scaling=1.0, n_held=64),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
    max_position=163840,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
))
