"""Plain reference of DeepSeek-V2-Lite's causal forward pass, one chip's
expert share included, written from the model's equations in
straightforward ``jax.numpy``: no cache, no kernels, no sorting or
grouping of tokens, no scan.  It imports nothing of the program.  The
caller sets the precision (the tests run it in float32 under
``jax.default_matmul_precision("highest")``).

Inputs are a flat ``path -> array`` dict in the program's parameter
layout (``stages/0/layers/0/mixer/wq``: stage 0 holds the leading dense
layers, stage 1 the MoE layers, stacked on a leading axis), a client's
LoRA factors as ``<path>/{a,b,mask}``, and a configuration dict with the
published model's key names (``hidden_size``, ``kv_lora_rank``,
``qk_rope_head_dim``, ``rope_scaling``, ...) plus ``router_experts`` (the
router's width) and ``first_held_expert``; ``n_routed_experts`` is the
number of experts this chip holds.

Per layer, with ``x`` the residual stream,
``P(h, w) = h·w + s·(h·A)·(mask·B)`` where a LoRA targets ``w``, and
``rms(h, g) = h / sqrt(mean(h²) + eps) · (1 + g)``:

    h = rms(x, norm1)
    q = P(h, wq) -> (q_nope, q_rope) per head       [no q compression]
    c, k_rope = rms((h·wkv_a)[:r], kv_norm), (h·wkv_a)[r:]   (one k_rope
                 for all heads)
    k_nope, v = P(c, wkv_b) per head
    q_rope, k_rope rotated by YaRN rope at each position
    a = softmax_causal((q_nope·k_nope + q_rope·k_rope) · scale) ,
        scale = (nope + rope)^-½ · m(factor, mscale_all_dim)²
    x = x + (a·v)·wo
    h = rms(x, norm2)
    dense layer:  x = x + (silu(h·wg) * (h·wu))·wd
    MoE layer:    g = softmax(h·router) over all router_experts (float32);
                  the top num_experts_per_tok (ids, weights); weights
                  divided by their sum only if norm_topk_prob; times
                  routed_scaling_factor;
                  x = x + Σ_{held e} w_e(token)·FFN_e(h) + FFN_shared(h)
                  with w_e = 0 where e is not among the token's top ids,
                  and FFN_shared one SwiGLU of width
                  n_shared_experts · moe_intermediate_size
    logits = rms(x, final_norm) · lm_head

YaRN (``rope_scaling`` of type yarn), on the rope dims d = qk_rope_head_dim:
``f_extra[i] = θ^(-2i/d)``, ``f_inter = f_extra / factor``,
``corr(r) = d·ln(L0 / (2π r)) / (2 ln θ)``, ``low = floor(corr(beta_fast))``,
``high = ceil(corr(beta_slow))`` clamped to [0, d-1],
``ramp[i] = clip((i - low)/(high - low), 0, 1)``,
``inv_freq = f_inter·ramp + f_extra·(1 - ramp)``; cos and sin times
``m(factor, mscale)/m(factor, mscale_all_dim)``, ``m(s, a) = 0.1·a·ln s + 1``.

Departures from the published model: the rope dims rotate in halves
(dims i and i + d/2 pair up) where the published checkpoint stores them
interleaved, which random weights make immaterial; norm weights are
stored as offsets from 1; only this chip's held experts contribute to
the routed sum, as the program's share computes it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _m(s: float, a: float) -> float:
    return 1.0 if s <= 1 else 0.1 * a * math.log(s) + 1.0


def yarn_inv_freq(cfg):
    d = cfg["qk_rope_head_dim"]
    theta = cfg["rope_theta"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extra = 1.0 / theta ** (2 * i / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return extra

    def corr(r):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (2 * math.pi * r)) / (2 * math.log(theta)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / rs["factor"] * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs:
        s *= _m(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _rope(x, pos, cfg):
    """x (b, s, heads, d) rotated in halves at positions ``pos`` (s,)."""
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    rs = cfg.get("rope_scaling")
    mul = (_m(rs["factor"], rs["mscale"]) / _m(rs["factor"],
                                                rs["mscale_all_dim"])
           if rs else 1.0)
    cos = (jnp.cos(ang) * mul)[None, :, None].astype(x.dtype)
    sin = (jnp.sin(ang) * mul)[None, :, None].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + g.astype(jnp.float32))).astype(x.dtype)


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def moe_layer(h, F, cfg):
    """One MoE layer's output for normed inputs ``h`` (..., d): the held
    experts' routed part plus the shared experts.  ``F`` holds the layer's
    ``router``, ``wg``/``wu``/``wd`` (held experts first) and
    ``shared/{wg,wu,wd}``."""
    gates = jax.nn.softmax(h.astype(jnp.float32)
                           @ F["router"].astype(jnp.float32), -1)
    w, ids = jax.lax.top_k(gates, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    routed = jnp.zeros_like(h)
    for j in range(cfg["n_routed_experts"]):
        e = cfg["first_held_expert"] + j
        we = jnp.where(ids == e, w, 0.0).sum(-1).astype(h.dtype)
        routed = routed + we[..., None] * _swiglu(h, F["wg"][j], F["wu"][j],
                                                  F["wd"][j])
    return routed + _swiglu(h, F["shared/wg"], F["shared/wu"],
                            F["shared/wd"])


def _layer_path(cfg, l):
    k = cfg["first_k_dense_replace"]
    return ("stages/0/layers/0/", l) if l < k else ("stages/1/layers/0/",
                                                    l - k)


def forward(P, lora, tokens, cfg, *, scale: float, dtype=jnp.float32,
            positions=None):
    """Logits (b, s, vocab) of every position of ``tokens`` (b, s), or of
    ``positions`` only (b, n, vocab), computed in ``dtype``."""
    P = {k: v.astype(dtype) if v.dtype != jnp.int32 else v
         for k, v in P.items()}
    lora = {k: v.astype(dtype) for k, v in lora.items()}
    eps = cfg["rms_norm_eps"]
    H = cfg["num_attention_heads"]
    r = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    b, s = tokens.shape
    pos = jnp.arange(s)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def proj(h, path, l):
        y = h @ P[path][l]
        if path + "/a" in lora:
            bb = lora[path + "/b"][l] * lora[path + "/mask"][l]
            y = y + scale * ((h @ lora[path + "/a"][l]) @ bb)
        return y

    x = P["embed"][tokens]
    for layer in range(cfg["num_hidden_layers"]):
        lp, l = _layer_path(cfg, layer)
        h = _rms(x, P[lp + "norm1/scale"][l], eps)
        q = proj(h, lp + "mixer/wq", l).reshape(b, s, H, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, cfg)
        kv_a = proj(h, lp + "mixer/wkv_a", l)
        c = _rms(kv_a[..., :r], P[lp + "mixer/kv_norm/scale"][l], eps)
        k_rope = _rope(kv_a[..., None, r:], pos, cfg)          # (b,s,1,dr)
        kv = proj(c, lp + "mixer/wkv_b", l).reshape(b, s, H, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        att = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
               + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]))
        att = att * jnp.asarray(softmax_scale(cfg), att.dtype)
        att = jnp.where(causal, att, jnp.asarray(-1e30, att.dtype))
        att = jax.nn.softmax(att.astype(jnp.float32), -1).astype(dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, H * dv)
        x = x + proj(o, lp + "mixer/wo", l)
        h = _rms(x, P[lp + "norm2/scale"][l], eps)
        f = lp + "ff/"
        if layer < cfg["first_k_dense_replace"]:
            x = x + _swiglu(h, P[f + "wg"][l], P[f + "wu"][l], P[f + "wd"][l])
            continue
        x = x + moe_layer(h, {k[len(f):]: v[l] for k, v in P.items()
                              if k.startswith(f)}, cfg)
    h = _rms(x, P["final_norm/scale"], eps)
    if positions is not None:
        h = h[:, positions]
    return (h @ P["lm_head"]).astype(jnp.float32)
