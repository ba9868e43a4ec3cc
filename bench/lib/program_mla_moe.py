"""The harness's adapter to the program's registry for latent-attention
MoE configurations (``bench/configs/deepseek-v2-lite.json``); the plain
reference never imports it.  ``lib/program.py`` is the dense one."""
from __future__ import annotations

import dataclasses


def program_config(cfg):
    """The program's registered ``ModelConfig`` for
    ``cfg["program_config"]`` with the sizes ``cfg`` states: the depth
    (``first_k_dense_replace`` dense layers, then MoE layers), the router's
    width (``router_experts``) and this chip's held experts
    (``n_routed_experts`` from ``first_held_expert``)."""
    from repro.configs import get_config
    m = get_config(cfg["program_config"])
    k, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    dense, moe = m.stages
    rs = cfg["rope_scaling"]
    return dataclasses.replace(
        m, d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=dataclasses.replace(
            m.rope_scaling, factor=rs["factor"],
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        stages=(dataclasses.replace(dense, repeats=k),
                dataclasses.replace(moe, repeats=L - k)),
        moe=dataclasses.replace(
            m.moe, n_experts=cfg["router_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_ff=cfg["moe_intermediate_size"],
            n_shared_experts=cfg["n_shared_experts"],
            norm_topk_prob=cfg["norm_topk_prob"],
            routed_scaling=float(cfg["routed_scaling_factor"]),
            n_held=cfg["n_routed_experts"],
            first_held=cfg["first_held_expert"]),
        mla=dataclasses.replace(
            m.mla, kv_lora_rank=cfg["kv_lora_rank"],
            q_lora_rank=cfg["q_lora_rank"],
            rope_head_dim=cfg["qk_rope_head_dim"],
            nope_head_dim=cfg["qk_nope_head_dim"],
            v_head_dim=cfg["v_head_dim"]))
