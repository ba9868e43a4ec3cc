"""Flash-decode Pallas kernel (TPU target): one query token vs a KV cache.

Grid: (batch·q_heads, n_kv_blocks) — the kv dimension iterates sequentially,
carrying online-softmax stats in VMEM scratch.  The current cache length
``pos+1`` arrives as a scalar-prefetch operand so the same compiled kernel
serves every decode step; blocks fully beyond the valid range contribute
nothing (masked), and on real TPU the grid can be truncated per step.

This is the serving hot spot of the PFTT personalized-LLM deployment
(EXPERIMENTS.md §Perf C/D); block shape (bk × head_dim) keeps the working
set ≪ VMEM for every assigned architecture.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, bk: int, n_kv_blocks: int, window: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale          # (1, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, bk)

    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = kpos <= pos
    if window > 0:
        mask &= kpos > pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0]).astype(jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_kernel(q, k_cache, v_cache, pos, *, window: int = 0,
                            bk: int = 128, interpret: bool | None = None):
    """q: (BH, 1, d); caches: (BK, Sc, d) with BH = BK·group; pos: scalar
    int32 (cache_len − 1).  Returns (BH, 1, d)."""
    bh, _, d = q.shape
    bkv, sc, _ = k_cache.shape
    group = bh // bkv
    bk = min(bk, sc)
    assert sc % bk == 0
    nk = sc // bk
    scale = d ** -0.5

    kernel = functools.partial(_kernel, scale=scale, bk=bk, n_kv_blocks=nk,
                               window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, j, pos_ref: (b, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, pos_ref, g=group:
                         (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, pos_ref, g=group:
                         (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, j, pos_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(jnp.asarray(pos, jnp.int32)[None], q, k_cache, v_cache)
