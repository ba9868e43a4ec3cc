"""Multi-head Latent Attention (DeepSeek-V2).

Sequence mode materializes per-head k/v from the compressed latent (fine with
remat); decode mode uses the *absorbed* formulation — q is projected into the
kv_lora latent space so attention runs directly against the compressed cache
(c_kv, k_rope), which is the whole point of MLA's small KV cache.

Factored-LoRA contract (the universal fused path): every entry point takes an
optional ``lora`` side channel — a dict mirroring the param leaves with
``{'a','b','mask'}`` factor dicts (``peft.init_lora``) on any of ``wq_a`` /
``wq_b`` / ``wkv_a`` / ``wkv_b`` / ``wo`` — plus ``scale`` (α/r) and
``backend``.  Targeted projections run ``peft.lora_proj``:

    y = x @ W + scale · ((x @ A) @ (mask · B))

so the dense (din, dout) delta is never formed and, under the cohort
engine's client-vmap, the frozen base stays UNBATCHED while only the rank-r
factors carry the client axis.  The one deliberate exception is absorbed
decode: ``mla_decode`` contracts q/ctx against ``wkv_b`` itself (not
``x @ W``), so ``wkv_b`` factors are merged into the LATENT-space weight
(kv_lora_rank × n_heads·(nope+v) — the same order as the factor's own B,
never a d_model² delta) via ``peft.effective_weight``.

With ``q_lora_rank=None`` (DeepSeek-V2-Lite) the query is projected
directly, ``q = x·wq`` with no ``q_norm``, and the side channel targets
``wq``.  With a YaRN ``rope_scaling`` the rope part rotates at YaRN's
frequencies and the softmax scale is ``(nope+rope)^-½·m²`` with
``m = 0.1·mscale_all_dim·ln(factor) + 1`` (``softmax_scale``), in sequence
mode and absorbed decode alike.  Decode's latent-space attention runs
under the ``absorb`` named scope (``mla/absorb`` in the device trace when
the caller opens ``mla``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig
from repro.models import attention as attn
from repro.models.norms import rmsnorm
from repro.models.peft import effective_weight, lora_proj
from repro.models.rope import apply_rope, yarn_mscale


def _lf(lora, key):
    """One leaf's factor dict from the mixer side channel (None-safe)."""
    return None if lora is None else lora.get(key)


def _attn_factor(scaling) -> float:
    """YaRN's ``m(factor, mscale_all_dim)²`` on the softmax scale (1
    without YaRN)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def softmax_scale(cfg: MLAConfig, scaling=None) -> float:
    """The attention softmax scale: ``(nope+rope)^-½``, times YaRN's
    ``m(factor, mscale_all_dim)²`` where rope scaling is YaRN."""
    s = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    m2 = _attn_factor(scaling)
    return s if m2 == 1.0 else s * m2


def init_mla(key, d_model: int, n_heads: int, cfg: MLAConfig, dtype):
    ks = jax.random.split(key, 5)
    qk = cfg.nope_head_dim + cfg.rope_head_dim
    std = d_model ** -0.5
    if cfg.q_lora_rank is None:
        q = {"wq": (jax.random.normal(ks[0], (d_model, n_heads * qk))
                    * std).astype(dtype)}
    else:
        q = {"wq_a": (jax.random.normal(ks[0], (d_model, cfg.q_lora_rank))
                      * std).astype(dtype),
             "q_norm": {"scale": jnp.zeros((cfg.q_lora_rank,), dtype)},
             "wq_b": (jax.random.normal(ks[1], (cfg.q_lora_rank, n_heads * qk))
                      * cfg.q_lora_rank ** -0.5).astype(dtype)}
    return {
        **q,
        "wkv_a": (jax.random.normal(ks[2], (d_model, cfg.kv_lora_rank + cfg.rope_head_dim))
                  * std).astype(dtype),
        "kv_norm": {"scale": jnp.zeros((cfg.kv_lora_rank,), dtype)},
        "wkv_b": (jax.random.normal(ks[3], (cfg.kv_lora_rank,
                                            n_heads * (cfg.nope_head_dim + cfg.v_head_dim)))
                  * cfg.kv_lora_rank ** -0.5).astype(dtype),
        "wo": (jax.random.normal(ks[4], (n_heads * cfg.v_head_dim, d_model))
               * (n_heads * cfg.v_head_dim) ** -0.5).astype(dtype),
    }


def _project_q(x, p, cfg: MLAConfig, n_heads: int, positions, rope_theta, eps,
               lora=None, scale: float = 1.0, backend: str = "jnp",
               rope_scaling=None):
    b, s, _ = x.shape
    if cfg.q_lora_rank is None:
        q = lora_proj(x, p["wq"], _lf(lora, "wq"), scale=scale,
                      backend=backend)
    else:
        cq = rmsnorm(lora_proj(x, p["wq_a"], _lf(lora, "wq_a"), scale=scale,
                               backend=backend), p["q_norm"]["scale"], eps)
        q = lora_proj(cq, p["wq_b"], _lf(lora, "wq_b"), scale=scale,
                      backend=backend)
    q = q.reshape(b, s, n_heads, cfg.nope_head_dim + cfg.rope_head_dim)
    q_nope, q_pe = q[..., :cfg.nope_head_dim], q[..., cfg.nope_head_dim:]
    q_pe = apply_rope(q_pe, positions, rope_theta, rope_scaling)
    return q_nope, q_pe


def _compress_kv(x, p, cfg: MLAConfig, positions, rope_theta, eps,
                 lora=None, scale: float = 1.0, backend: str = "jnp",
                 rope_scaling=None):
    kv_a = lora_proj(x, p["wkv_a"], _lf(lora, "wkv_a"), scale=scale,
                     backend=backend)
    c_kv = rmsnorm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"]["scale"], eps)
    k_pe = apply_rope(kv_a[..., None, cfg.kv_lora_rank:], positions,
                      rope_theta, rope_scaling)
    return c_kv, k_pe[..., 0, :]                       # (B,S,r), (B,S,rope_hd)


def mla_seq(x, p, cfg: MLAConfig, n_heads: int, positions, rope_theta: float,
            eps: float, *, causal: bool = True, impl: str = "auto",
            sparse_cfg=None, q_offset: int = 0, causal_skip: bool = False,
            lora=None, scale: float = 1.0, backend: str = "jnp",
            rope_scaling=None):
    """Full-sequence MLA (train / prefill).  Returns (y, (c_kv, k_pe)).
    ``lora``/``scale``/``backend``: the factored-LoRA side channel (module
    docstring) — every projection stays unmerged."""
    b, s, _ = x.shape
    q_nope, q_pe = _project_q(x, p, cfg, n_heads, positions, rope_theta, eps,
                              lora=lora, scale=scale, backend=backend,
                              rope_scaling=rope_scaling)
    c_kv, k_pe = _compress_kv(x, p, cfg, positions, rope_theta, eps,
                              lora=lora, scale=scale, backend=backend,
                              rope_scaling=rope_scaling)
    kv = lora_proj(c_kv, p["wkv_b"], _lf(lora, "wkv_b"), scale=scale,
                   backend=backend).reshape(
        b, s, n_heads, cfg.nope_head_dim + cfg.v_head_dim)
    k_nope, v = kv[..., :cfg.nope_head_dim], kv[..., cfg.nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None],
                                  (b, s, n_heads, cfg.rope_head_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    # the attention functions scale by (nope+rope)^-½; YaRN's m² rides on q
    m2 = _attn_factor(rope_scaling)
    if m2 != 1.0:
        q = q * jnp.asarray(m2, q.dtype)
    if impl == "sparse" and sparse_cfg is not None:
        y = attn.block_sparse_attention(q, k, v, sparse_cfg, q_offset=q_offset)
    elif impl == "dense" or s <= 2048:
        y = attn.dense_attention(q, k, v, causal=causal, q_offset=q_offset)
    elif causal and causal_skip:
        y = attn.chunked_attention_pairs(q, k, v, causal=True,
                                         q_offset=q_offset)
    else:
        y = attn.chunked_attention(q, k, v, causal=causal, q_offset=q_offset)
    y = lora_proj(y.reshape(b, s, n_heads * cfg.v_head_dim), p["wo"],
                  _lf(lora, "wo"), scale=scale, backend=backend)
    return y, (c_kv, k_pe)


def mla_decode(x, p, cfg: MLAConfig, n_heads: int, pos, rope_theta: float,
               eps: float, ckv_cache, kpe_cache, *, sparse_cfg=None,
               lora=None, scale: float = 1.0, backend: str = "jnp",
               rope_scaling=None):
    """Absorbed-MLA decode.  x: (B,1,d); caches: (B,Sc,r) / (B,Sc,rope_hd);
    ``pos``: traced scalar — index the new token was written at.
    Caller must have already written the new (c_kv, k_pe) at ``pos``.
    ``wkv_b`` factors merge into the latent-space weight here
    (``peft.effective_weight`` — see module docstring); q/o projections stay
    factored."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos)
    q_nope, q_pe = _project_q(x, p, cfg, n_heads, positions, rope_theta, eps,
                              lora=lora, scale=scale, backend=backend,
                              rope_scaling=rope_scaling)
    r = cfg.kv_lora_rank
    wkv_b = effective_weight(p["wkv_b"], _lf(lora, "wkv_b"), scale).reshape(
        r, n_heads, cfg.nope_head_dim + cfg.v_head_dim)
    wk_b, wv_b = wkv_b[..., :cfg.nope_head_dim], wkv_b[..., cfg.nope_head_dim:]
    with jax.named_scope("absorb"):
        v_out = _absorbed_attention(q_nope, q_pe, wk_b, wv_b, ckv_cache,
                                    kpe_cache, pos, sparse_cfg,
                                    softmax_scale(cfg, rope_scaling))
    y = lora_proj(v_out.reshape(b, 1, n_heads * cfg.v_head_dim).astype(x.dtype),
                  p["wo"], _lf(lora, "wo"), scale=scale, backend=backend)
    return y


def _absorbed_attention(q_nope, q_pe, wk_b, wv_b, ckv_cache, kpe_cache, pos,
                        sparse_cfg, att_scale):
    """One new token's attention in the latent space: q folded through
    ``wk_b`` against the compressed cache, the latent context unfolded
    through ``wv_b``.  Returns (B, H, v_head_dim) float32."""
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    logits = (jnp.einsum("bhr,btr->bht", q_abs, ckv_cache.astype(jnp.float32))
              + jnp.einsum("bhp,btp->bht", q_pe[:, 0].astype(jnp.float32),
                           kpe_cache.astype(jnp.float32))) * att_scale
    sc = ckv_cache.shape[1]
    slot = jnp.arange(sc)
    allowed = slot <= pos
    if sparse_cfg is not None:
        bs = sparse_cfg.block_size
        blk, qblk = slot // bs, pos // bs
        a = (blk < sparse_cfg.sink_blocks)
        a |= blk > qblk - sparse_cfg.local_blocks
        a |= (blk % sparse_cfg.stride) == 0
        allowed &= a
    logits = jnp.where(allowed[None, None], logits, attn.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", probs, ckv_cache.astype(jnp.float32))
    return jnp.einsum("bhr,rhv->bhv", ctx, wv_b.astype(jnp.float32))
