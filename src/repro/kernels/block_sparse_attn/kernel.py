"""Block-sparse attention Pallas kernel — the paper's sparse-attention
device as a TPU kernel.

The static sparsity pattern (sink blocks + local band + strided global
blocks, see ``repro.models.attention.sparse_block_table``) is passed as a
scalar-prefetch operand: the grid's last dimension enumerates only the
ACTIVE kv blocks per q block (A ≪ n_kv_blocks), and the kv BlockSpec index
map reads the actual block id from the prefetched table.  Compute and HBM
traffic are therefore O(S·A·block) — genuinely sub-quadratic, matching the
gather-based jnp lowering.

Invalid table slots point at block 0 with a mask that voids their
contribution (positions > qpos are masked anyway for the causal diagonal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(idx_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, bq: int, bk: int,
            n_active: int):
    i = pl.program_id(1)
    a = pl.program_id(2)

    @pl.when(a == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))

    blk = idx_ref[i, a]
    ok = valid_ref[i, a]
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (kpos <= qpos) & (ok > 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0]).astype(jnp.float32)
    m_ref[...] = m_new

    @pl.when(a == n_active - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def block_sparse_attention_kernel(q, k, v, idx, valid, *, block: int,
                                  interpret: bool | None = None):
    """q: (BH, Sq, d); k/v: (BK, Sk, d); idx/valid: (n_q_blocks, A) static
    tables from ``sparse_block_table``.  Returns (BH, Sq, d)."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    assert sq % block == 0 and sk % block == 0
    nq = sq // block
    n_active = idx.shape[1]
    scale = d ** -0.5

    kernel = functools.partial(_kernel, scale=scale, bq=block, bk=block,
                               n_active=n_active)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, n_active),
        in_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, i, a, idx_ref, valid_ref: (b, i, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, i, a, idx_ref, valid_ref, g=group:
                         (b // g, idx_ref[i, a], 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, i, a, idx_ref, valid_ref, g=group:
                         (b // g, idx_ref[i, a], 0)),
        ],
        out_specs=pl.BlockSpec((1, block, d),
                               lambda b, i, a, idx_ref, valid_ref: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(idx, valid, q, k, v)
