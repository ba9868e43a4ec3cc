"""Flash attention Pallas kernel (TPU target).

Grid: (batch·q_heads, n_q_blocks, n_kv_blocks) — the kv dimension iterates
sequentially per (bh, i), carrying the online-softmax state (m, l, acc) in
VMEM scratch.  GQA is handled in the index map: the kv operand is indexed by
``bh // group`` so grouped heads share kv blocks without materializing a
broadcast.  Causal masking is positional; on real TPU the diagonal-block
skip (j > i never contributes) is a grid-size optimization — see
EXPERIMENTS.md §Perf.

Block shapes default to (128, head_dim): 128 is the MXU tile edge, and the
working set per step (q, k, v blocks + acc) stays ≪ 16 MiB VMEM for all
head_dims used by the assigned architectures (64…256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            n_kv_blocks: int):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
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


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 128, bk: int = 128,
                           interpret: bool | None = None):
    """q: (BH, Sq, d); k/v: (BK, Sk, d) with BH = BK·group.
    Returns (BH, Sq, d)."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0
    nq, nk = sq // bq, sk // bk
    scale = d ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk,
        n_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
