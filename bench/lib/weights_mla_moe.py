"""Seeded random weights and the client LoRA of a latent-attention MoE
configuration (``bench/configs/deepseek-v2-lite.json``), made by the
benchmark in the program's parameter layout: stage 0 holds the
``first_k_dense_replace`` dense layers, stage 1 the MoE layers, each
stacked on a leading axis.  Leaves are drawn as ``lib/weights.py`` draws
them (``fold_in(key, crc32(path))``), all in one jitted call; norm scales
are offsets from 1, as the program stores them."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from lib import weights

LN_NOISE, SMALL_STD = weights.LN_NOISE, weights.SMALL_STD


def _stages(cfg):
    k = cfg["first_k_dense_replace"]
    return ((0, k), (1, cfg["num_hidden_layers"] - k))


def model_leaves(cfg) -> Dict[str, Tuple[tuple, str, float]]:
    """path -> (shape, init, std) of the base model."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    dff, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    eh, sf = cfg["n_routed_experts"], cfg["n_shared_experts"] * f
    out = {"embed": ((V, d), "normal", SMALL_STD),
           "lm_head": ((d, V), "normal", SMALL_STD),
           "final_norm/scale": ((d,), "normal", LN_NOISE)}
    for si, n in _stages(cfg):
        lp = f"stages/{si}/layers/0/"
        out[lp + "norm1/scale"] = ((n, d), "normal", LN_NOISE)
        out[lp + "norm2/scale"] = ((n, d), "normal", LN_NOISE)
        out[lp + "mixer/wq"] = ((n, d, H * (dn + dr)), "normal", d ** -0.5)
        out[lp + "mixer/wkv_a"] = ((n, d, r + dr), "normal", d ** -0.5)
        out[lp + "mixer/kv_norm/scale"] = ((n, r), "normal", LN_NOISE)
        out[lp + "mixer/wkv_b"] = ((n, r, H * (dn + dv)), "normal", r ** -0.5)
        out[lp + "mixer/wo"] = ((n, H * dv, d), "normal", (H * dv) ** -0.5)
        if si == 0:
            out[lp + "ff/wg"] = ((n, d, dff), "normal", d ** -0.5)
            out[lp + "ff/wu"] = ((n, d, dff), "normal", d ** -0.5)
            out[lp + "ff/wd"] = ((n, dff, d), "normal", dff ** -0.5)
            continue
        out[lp + "ff/router"] = ((n, d, cfg["router_experts"]), "normal",
                                 d ** -0.5)
        out[lp + "ff/wg"] = ((n, eh, d, f), "normal", d ** -0.5)
        out[lp + "ff/wu"] = ((n, eh, d, f), "normal", d ** -0.5)
        out[lp + "ff/wd"] = ((n, eh, f, d), "normal", f ** -0.5)
        out[lp + "ff/shared/wg"] = ((n, d, sf), "normal", d ** -0.5)
        out[lp + "ff/shared/wu"] = ((n, d, sf), "normal", d ** -0.5)
        out[lp + "ff/shared/wd"] = ((n, sf, d), "normal", sf ** -0.5)
    return out


def make_params(cfg, key, dtype=jnp.float32) -> Dict[str, jax.Array]:
    """The base model's flat path -> array dict, in one jitted call."""
    leaves = model_leaves(cfg)

    def build(k):
        return {p: weights._draw(k, p, *spec, dtype)
                for p, spec in leaves.items()}

    return jax.jit(build)(key)


def lora_leaves(cfg) -> Dict[str, Tuple[tuple, tuple]]:
    """Target weight path -> (A shape, B shape) of one client's LoRA, on
    every stage's ``peft.lora_targets``."""
    r = cfg["peft"]["lora_rank"]
    shapes = model_leaves(cfg)
    out = {}
    for si, n in _stages(cfg):
        for t in cfg["peft"]["lora_targets"]:
            path = f"stages/{si}/layers/0/{t}"
            _, din, dout = shapes[path][0]
            out[path] = ((n, din, r), (n, r, dout))
    return out


def make_lora(cfg, key, cid: int = 0, dtype=jnp.float32):
    """One client's flat ``<target>/{a,b,mask}`` factors, drawn from
    ``fold_in(key, cid)`` as ``lib/weights.py::client_lora`` draws them
    (B drawn too, not zero, as a trained client's is)."""
    kc = jax.random.fold_in(key, cid)

    def build(kc):
        out = {}
        for path, (sa, sb) in lora_leaves(cfg).items():
            out[path + "/a"] = (jax.random.normal(
                weights._leaf_key(kc, path + "/a"), sa)
                * sa[-2] ** -0.5).astype(dtype)
            out[path + "/b"] = (jax.random.normal(
                weights._leaf_key(kc, path + "/b"), sb)
                * SMALL_STD).astype(dtype)
            out[path + "/mask"] = jnp.ones((sa[0], 1, 1), dtype)
        return out

    return jax.jit(build)(kc)
