"""Layer-kind dispatch: init / full-sequence forward / single-token decode.

A layer is (mixer, ff) with pre-norm residual structure:

    x = x + mixer(norm1(x))          [dec adds a cross-attention sublayer]
    x = x + ff(norm2(x))             [if ff != none]

All functions are scan-friendly: parameters for a repeated pattern position
are stacked along a leading repeat axis by ``transformer.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerKind, ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import ssm as ssm_mod
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe_layer
from repro.models.norms import apply_norm
from repro.models.peft import LoraProj, has_factors, merge_factors
from repro.models.rope import apply_rope
from repro.sharding import MeshCtx


@dataclasses.dataclass
class LayerCtx:
    """Trace-time context threaded through layer application."""
    cfg: ModelConfig
    meshctx: Optional[MeshCtx]
    positions: Any            # (S,) or (B,S) int — absolute positions
    impl: str = "auto"        # auto | dense | chunked | sparse
    memory: Any = None        # encoder output for cross-attention
    q_offset: Any = 0
    mode: str = "train"       # train | prefill | decode
    pos: Any = None           # decode: traced scalar write position
    causal: bool = True
    opts: dict = dataclasses.field(default_factory=dict)  # §Perf knobs
    lora_scale: float = 1.0   # α/r for factored LoRA side-channel trees


def _sub(lora, *keys):
    """Navigate a lora side-channel subtree; None anywhere → None."""
    for k in keys:
        if lora is None:
            return None
        lora = lora.get(k)
    return lora


def _proj(x, w, lf, ctx: LayerCtx):
    """LoRA-aware projection: factored ``LoraProj`` when factors ride
    along, plain matmul otherwise."""
    return LoraProj(w, lf, ctx.lora_scale,
                    ctx.opts.get("lora_backend", "jnp"))(x)


def _lkw(ctx: LayerCtx, mf):
    """Factored side-channel kwargs for mla/ssm entry points."""
    return dict(lora=mf, scale=ctx.lora_scale,
                backend=ctx.opts.get("lora_backend", "jnp"))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_norm(cfg: ModelConfig, dim: int, dtype):
    p = {"scale": jnp.zeros((dim,), dtype)}
    if cfg.norm == "ln":
        p["scale"] = jnp.ones((dim,), dtype)
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def _init_attn_proj(key, cfg: ModelConfig, dtype):
    d, h, k_, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    std = d ** -0.5
    return {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * std).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, k_ * hd)) * std).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, k_ * hd)) * std).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }


def init_layer(key, cfg: ModelConfig, kind: LayerKind, dtype):
    ks = jax.random.split(key, 4)
    p: dict = {"norm1": _init_norm(cfg, cfg.d_model, dtype)}
    if kind.mixer in ("attn", "local", "enc", "dec"):
        p["mixer"] = _init_attn_proj(ks[0], cfg, dtype)
        if kind.mixer == "dec":
            p["cross"] = _init_attn_proj(ks[3], cfg, dtype)
            p["norm_x"] = _init_norm(cfg, cfg.d_model, dtype)
    elif kind.mixer == "mla":
        p["mixer"] = mla_mod.init_mla(ks[0], cfg.d_model, cfg.n_heads, cfg.mla, dtype)
    elif kind.mixer == "mamba":
        p["mixer"] = ssm_mod.init_mamba(ks[0], cfg.d_model, cfg.ssm, dtype)
    if kind.ff == "mlp":
        p["norm2"] = _init_norm(cfg, cfg.d_model, dtype)
        p["ff"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    elif kind.ff == "moe":
        p["norm2"] = _init_norm(cfg, cfg.d_model, dtype)
        p["ff"] = init_moe(ks[1], cfg.d_model, cfg.moe, cfg.act, dtype)
    return p


# ---------------------------------------------------------------------------
# attention helpers
# ---------------------------------------------------------------------------


def _qkv(xn, mp, cfg: ModelConfig, positions, use_rope: bool,
         lf=None, ctx: Optional[LayerCtx] = None):
    b, s, _ = xn.shape
    h, k_, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if lf is None or ctx is None:
        q = (xn @ mp["wq"]).reshape(b, s, h, hd)
        k = (xn @ mp["wk"]).reshape(b, s, k_, hd)
        v = (xn @ mp["wv"]).reshape(b, s, k_, hd)
    else:
        q = _proj(xn, mp["wq"], _sub(lf, "wq"), ctx).reshape(b, s, h, hd)
        k = _proj(xn, mp["wk"], _sub(lf, "wk"), ctx).reshape(b, s, k_, hd)
        v = _proj(xn, mp["wv"], _sub(lf, "wv"), ctx).reshape(b, s, k_, hd)
    if use_rope and cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_core_seq(q, k, v, kind: LayerKind, cfg: ModelConfig, ctx: LayerCtx):
    s = q.shape[1]
    causal = ctx.causal and kind.mixer != "enc"
    window = cfg.window if kind.mixer == "local" else 0
    if kind.mixer in ("attn", "dec") and ctx.impl == "sparse" and cfg.sparse_attn:
        return attn.block_sparse_attention(q, k, v, cfg.sparse_attn,
                                           q_offset=ctx.q_offset)
    if ctx.impl == "dense" or s <= 2048:
        return attn.dense_attention(q, k, v, causal=causal, window=window,
                                    q_offset=ctx.q_offset)
    if causal and ctx.opts.get("causal_skip"):
        return attn.chunked_attention_pairs(q, k, v, causal=True,
                                            window=window,
                                            q_offset=ctx.q_offset)
    return attn.chunked_attention(q, k, v, causal=causal, window=window,
                                  q_offset=ctx.q_offset)


# ---------------------------------------------------------------------------
# full-sequence layer application
# ---------------------------------------------------------------------------


def apply_layer_seq(x, lp, kind: LayerKind, ctx: LayerCtx, lora=None):
    """Returns (x, cache_entry, aux).  cache_entry is the per-layer state to
    seed a decode cache (k/v, compressed kv, or ssm states).  ``lora`` is the
    layer's factor subtree (mirrors ``lp``; None → dense path)."""
    cfg = ctx.cfg
    xn = apply_norm(x, lp["norm1"], cfg.norm, cfg.norm_eps)
    cache_entry = None
    aux = jnp.zeros((), jnp.float32)

    if kind.mixer in ("attn", "local", "enc", "dec"):
        mf = _sub(lora, "mixer")
        q, k, v = _qkv(xn, lp["mixer"], cfg, ctx.positions, use_rope=True,
                       lf=mf, ctx=ctx)
        y = _attn_core_seq(q, k, v, kind, cfg, ctx)
        b, s = y.shape[:2]
        x = x + _proj(y.reshape(b, s, -1), lp["mixer"]["wo"],
                      _sub(mf, "wo"), ctx)
        if kind.mixer != "enc":
            cache_entry = {"k": k, "v": v}
        if kind.mixer == "dec":
            cf = _sub(lora, "cross")
            xn2 = apply_norm(x, lp["norm_x"], cfg.norm, cfg.norm_eps)
            qx = _proj(xn2, lp["cross"]["wq"], _sub(cf, "wq"),
                       ctx).reshape(b, s, cfg.n_heads, cfg.hd)
            mem = ctx.memory
            kx = _proj(mem, lp["cross"]["wk"], _sub(cf, "wk"), ctx).reshape(
                mem.shape[0], mem.shape[1], cfg.n_kv_heads, cfg.hd)
            vx = _proj(mem, lp["cross"]["wv"], _sub(cf, "wv"), ctx).reshape(
                mem.shape[0], mem.shape[1], cfg.n_kv_heads, cfg.hd)
            yx = attn.dense_attention(qx, kx, vx, causal=False)
            x = x + _proj(yx.reshape(b, s, -1), lp["cross"]["wo"],
                          _sub(cf, "wo"), ctx)
            cache_entry["xk"] = kx
            cache_entry["xv"] = vx
    elif kind.mixer == "mla":
        # factored path: mla takes the lora side channel directly — the
        # frozen base is never re-materialized under the client vmap
        mf = _sub(lora, "mixer")
        impl = ctx.impl if ctx.impl != "auto" else (
            "dense" if x.shape[1] <= 2048 else "chunked")
        with jax.named_scope("mla"):
            y, (ckv, kpe) = mla_mod.mla_seq(
                xn, lp["mixer"], cfg.mla, cfg.n_heads, ctx.positions,
                cfg.rope_theta, cfg.norm_eps, causal=ctx.causal, impl=impl,
                sparse_cfg=cfg.sparse_attn, q_offset=ctx.q_offset,
                causal_skip=ctx.opts.get("causal_skip", False),
                rope_scaling=cfg.rope_scaling, **_lkw(ctx, mf))
        x = x + y
        cache_entry = {"ckv": ckv, "kpe": kpe}
    elif kind.mixer == "mamba":
        mf = _sub(lora, "mixer")
        if (ctx.opts.get("mamba_sp") and ctx.mode == "train"
                and ctx.meshctx is not None and not has_factors(mf)):
            # sequence-parallel SSD: activations stay seq-sharded (§Perf B2);
            # its shard_map replicates raw weights, so factored layers route
            # through the plain factored mamba_seq below instead
            x = x + ssm_mod.mamba_seq_sp(xn, lp["mixer"], cfg.ssm,
                                         cfg.d_model, cfg.norm_eps,
                                         ctx.meshctx)
        else:
            y, (h_final, conv_state) = ssm_mod.mamba_seq(
                xn, lp["mixer"], cfg.ssm, cfg.d_model, cfg.norm_eps,
                **_lkw(ctx, mf))
            x = x + y
            cache_entry = {"h": h_final, "conv": conv_state}

    if kind.ff != "none":
        xn2 = apply_norm(x, lp["norm2"], cfg.norm, cfg.norm_eps)
        if kind.ff == "mlp":
            x = x + mlp(xn2, lp["ff"], cfg.act, lora=_sub(lora, "ff"),
                        scale=ctx.lora_scale,
                        backend=ctx.opts.get("lora_backend", "jnp"))
        else:
            fp = merge_factors(lp["ff"], _sub(lora, "ff"), ctx.lora_scale)
            y, aux, counts = moe_layer(xn2, fp, cfg.moe, ctx.meshctx, cfg.act,
                                       a2a=ctx.opts.get("moe_a2a", False))
            x = x + y
            if counts:
                cache_entry = {**(cache_entry or {}), **counts}
    if "adapter" in lp:  # PFTT universal adapter (bottleneck + residual)
        from repro.models.peft import adapter_fwd
        x = adapter_fwd(x, lp["adapter"])
    return x, cache_entry, aux


# ---------------------------------------------------------------------------
# decode layer application
# ---------------------------------------------------------------------------


def _cache_write(cache, new, slot):
    """Write one token's k/v (B,1,K,hd) at ``slot`` (traced scalar)."""
    return jax.lax.dynamic_update_slice_in_dim(cache, new.astype(cache.dtype),
                                               slot, axis=1)


def apply_layer_decode(x, lp, kind: LayerKind, cache, ctx: LayerCtx,
                       lora=None):
    """x: (B,1,d).  Returns (x, new_cache).  ``lora`` as in
    ``apply_layer_seq`` (factored serving: base stays unmerged)."""
    cfg = ctx.cfg
    pos = ctx.pos
    xn = apply_norm(x, lp["norm1"], cfg.norm, cfg.norm_eps)
    new_cache = cache
    moe_counts = {}

    def _ff(x, lq=lora):
        xn2 = apply_norm(x, lp["norm2"], cfg.norm, cfg.norm_eps)
        if kind.ff == "mlp":
            return x + mlp(xn2, lp["ff"], cfg.act, lora=_sub(lq, "ff"),
                           scale=ctx.lora_scale,
                           backend=ctx.opts.get("lora_backend", "jnp"))
        fp = merge_factors(lp["ff"], _sub(lq, "ff"), ctx.lora_scale)
        y, _, counts = moe_layer(xn2, fp, cfg.moe, ctx.meshctx, cfg.act)
        moe_counts.update(counts)
        return x + y

    def _counted(c):
        """The cache with this step's expert-layer counters added to the
        running totals that prefill started."""
        return dict(c, **{k: cache[k] + v for k, v in moe_counts.items()})

    if kind.mixer in ("attn", "local", "dec"):
        mf = _sub(lora, "mixer")
        positions = jnp.full((x.shape[0], 1), pos)
        q, k, v = _qkv(xn, lp["mixer"], cfg, positions, use_rope=True,
                       lf=mf, ctx=ctx)
        if "k_pers" in cache:  # sparse KV cache (§Perf C)
            new_cache = attn.sparse_kv_write(cache, k, v, pos,
                                             cfg.sparse_attn,
                                             ctx.opts["sparse_kv_seq"])
            y = attn.sparse_kv_decode(q, new_cache, pos, cfg.sparse_attn,
                                      ctx.opts["sparse_kv_seq"])
            x = x + _proj(y.reshape(x.shape[0], 1, -1), lp["mixer"]["wo"],
                          _sub(mf, "wo"), ctx)
            if kind.ff != "none":
                x = _ff(x)
            if "adapter" in lp:
                from repro.models.peft import adapter_fwd
                x = adapter_fwd(x, lp["adapter"])
            return x, _counted(new_cache)
        sc = cache["k"].shape[1]
        ring = kind.mixer == "local" and cfg.window > 0 and sc <= cfg.window
        slot = jnp.mod(pos, sc) if ring else jnp.minimum(pos, sc - 1)
        kc = _cache_write(cache["k"], k, slot)
        vc = _cache_write(cache["v"], v, slot)
        sparse = cfg.sparse_attn if (ctx.impl == "sparse" and kind.mixer != "local") else None
        if sparse is not None and not ring and ctx.opts.get("sparse_gather_decode"):
            y = attn.sparse_gather_decode(q, kc, vc, pos, sparse)
        else:
            y = attn.decode_attention(
                q, kc, vc, pos + 1,
                window=cfg.window if kind.mixer == "local" else 0,
                sparse=sparse, ring=ring)
        x = x + _proj(y.reshape(x.shape[0], 1, -1), lp["mixer"]["wo"],
                      _sub(mf, "wo"), ctx)
        new_cache = dict(cache, k=kc, v=vc)
        if kind.mixer == "dec":
            cf = _sub(lora, "cross")
            xn2 = apply_norm(x, lp["norm_x"], cfg.norm, cfg.norm_eps)
            qx = _proj(xn2, lp["cross"]["wq"], _sub(cf, "wq"), ctx).reshape(
                x.shape[0], 1, cfg.n_heads, cfg.hd)
            yx = attn.decode_attention(qx, cache["xk"], cache["xv"],
                                       cache["xk"].shape[1])
            x = x + _proj(yx.reshape(x.shape[0], 1, -1), lp["cross"]["wo"],
                          _sub(cf, "wo"), ctx)
    elif kind.mixer == "mla":
        mf = _sub(lora, "mixer")
        with jax.named_scope("mla"):
            c_kv, k_pe = mla_mod._compress_kv(
                xn, lp["mixer"], cfg.mla, jnp.full((x.shape[0], 1), pos),
                cfg.rope_theta, cfg.norm_eps,
                rope_scaling=cfg.rope_scaling, **_lkw(ctx, mf))
            ckv = _cache_write(cache["ckv"], c_kv, pos)
            kpe = _cache_write(cache["kpe"], k_pe, pos)
            sparse = cfg.sparse_attn if ctx.impl == "sparse" else None
            y = mla_mod.mla_decode(xn, lp["mixer"], cfg.mla, cfg.n_heads,
                                   pos, cfg.rope_theta, cfg.norm_eps, ckv,
                                   kpe, sparse_cfg=sparse,
                                   rope_scaling=cfg.rope_scaling,
                                   **_lkw(ctx, mf))
        x = x + y
        new_cache = dict(cache, ckv=ckv, kpe=kpe)
    elif kind.mixer == "mamba":
        mf = _sub(lora, "mixer")
        y, (h, conv) = ssm_mod.mamba_decode(
            xn, lp["mixer"], cfg.ssm, cfg.d_model, cfg.norm_eps,
            cache["h"], cache["conv"], **_lkw(ctx, mf))
        x = x + y
        new_cache = dict(cache, h=h, conv=conv)

    if kind.ff != "none":
        x = _ff(x)
    if "adapter" in lp:
        from repro.models.peft import adapter_fwd
        x = adapter_fwd(x, lp["adapter"])
    return x, _counted(new_cache)


# ---------------------------------------------------------------------------
# cache shapes / init
# ---------------------------------------------------------------------------


def moe_counter_shapes(n_held: int):
    """Cache entries of a held-expert MoE layer's counters (running
    totals since prefill, ``moe.moe_held``): not positions of the
    sequence."""
    return {"moe_rows": ((n_held,), jnp.int32),
            "moe_hits": ((), jnp.int32),
            "moe_dropped": ((), jnp.int32)}


def layer_cache_shape(cfg: ModelConfig, kind: LayerKind, batch: int,
                      cache_len: int, dtype, sparse_kv: bool = False):
    """Abstract cache entry for one layer (no leading repeat axis): the
    mixer's state, and a held-expert MoE layer's counters."""
    c = _mixer_cache_shape(cfg, kind, batch, cache_len, dtype, sparse_kv)
    if kind.ff == "moe" and cfg.moe.n_held:
        c = {**c, **moe_counter_shapes(cfg.moe.n_held)}
    return c


def _mixer_cache_shape(cfg: ModelConfig, kind: LayerKind, batch: int,
                       cache_len: int, dtype, sparse_kv: bool):
    if sparse_kv and kind.mixer == "attn" and cfg.sparse_attn is not None:
        from repro.models.attention import sparse_kv_layout
        _, _, ring_slots, n_pers = sparse_kv_layout(cache_len, cfg.sparse_attn)
        kk, hd = cfg.n_kv_heads, cfg.hd
        return {"k_pers": ((batch, n_pers, kk, hd), dtype),
                "v_pers": ((batch, n_pers, kk, hd), dtype),
                "k_ring": ((batch, ring_slots, kk, hd), dtype),
                "v_ring": ((batch, ring_slots, kk, hd), dtype)}
    if kind.mixer in ("attn", "dec"):
        c = {"k": ((batch, cache_len, cfg.n_kv_heads, cfg.hd), dtype),
             "v": ((batch, cache_len, cfg.n_kv_heads, cfg.hd), dtype)}
        if kind.mixer == "dec":
            c["xk"] = ((batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.hd), dtype)
            c["xv"] = ((batch, cfg.encoder_seq, cfg.n_kv_heads, cfg.hd), dtype)
        return c
    if kind.mixer == "local":
        sc = min(cache_len, cfg.window) if cfg.window else cache_len
        return {"k": ((batch, sc, cfg.n_kv_heads, cfg.hd), dtype),
                "v": ((batch, sc, cfg.n_kv_heads, cfg.hd), dtype)}
    if kind.mixer == "mla":
        m = cfg.mla
        return {"ckv": ((batch, cache_len, m.kv_lora_rank), dtype),
                "kpe": ((batch, cache_len, m.rope_head_dim), dtype)}
    if kind.mixer == "mamba":
        s = cfg.ssm
        d_in = cfg.d_inner
        h = cfg.ssm_heads
        conv_dim = d_in + 2 * s.n_groups * s.state
        return {"h": ((batch, h, s.headdim, s.state), jnp.float32),
                "conv": ((batch, s.conv_width - 1, conv_dim), dtype)}
    return {}


# ---------------------------------------------------------------------------
# analytic parameter counts (accounting / roofline)
# ---------------------------------------------------------------------------


def layer_param_count(cfg: ModelConfig, kind: LayerKind,
                      active_only: bool = False) -> int:
    d = cfg.d_model
    n = d  # norm1
    if kind.mixer in ("attn", "local", "enc", "dec"):
        n += d * cfg.n_heads * cfg.hd * 2 + d * cfg.n_kv_heads * cfg.hd * 2
        if kind.mixer == "dec":
            n += d * cfg.n_heads * cfg.hd * 2 + d * cfg.n_kv_heads * cfg.hd * 2 + d
    elif kind.mixer == "mla":
        m = cfg.mla
        qk = m.nope_head_dim + m.rope_head_dim
        q = (d * cfg.n_heads * qk if m.q_lora_rank is None else
             d * m.q_lora_rank + m.q_lora_rank
             + m.q_lora_rank * cfg.n_heads * qk)
        n += (q + d * (m.kv_lora_rank + m.rope_head_dim) + m.kv_lora_rank
              + m.kv_lora_rank * cfg.n_heads * (m.nope_head_dim + m.v_head_dim)
              + cfg.n_heads * m.v_head_dim * d)
    elif kind.mixer == "mamba":
        s = cfg.ssm
        d_in = cfg.d_inner
        h = cfg.ssm_heads
        conv_dim = d_in + 2 * s.n_groups * s.state
        proj_out = 2 * d_in + 2 * s.n_groups * s.state + h
        n += (d * proj_out + s.conv_width * conv_dim + conv_dim
              + 3 * h + d_in + d_in * d)
    if kind.ff == "mlp":
        mult = 3 if cfg.act in ("swiglu", "geglu") else 2
        n += d + mult * d * cfg.d_ff
    elif kind.ff == "moe":
        m = cfg.moe
        mult = 3 if cfg.act in ("swiglu", "geglu") else 2
        e = min(m.top_k, m.n_slab) if active_only else m.n_slab
        n += d + d * m.n_experts + e * mult * d * m.d_ff
        if m.n_shared_experts:
            n += mult * d * (m.n_shared_experts * m.d_ff)
    return n
