"""Public wrapper for the block-sparse attention kernel — model layout,
pattern table construction from the config."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import SparseAttnConfig
from repro.kernels.block_sparse_attn.kernel import block_sparse_attention_kernel
from repro.models.attention import sparse_block_table


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def block_sparse_attention(q, k, v, cfg: SparseAttnConfig, *,
                           interpret: bool | None = None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd) → (B, Sq, H, hd)."""
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    g = h // kh
    bs = cfg.block_size
    idx_np, valid_np = sparse_block_table(sq // bs, sk // bs, cfg)
    idx = jnp.asarray(idx_np)
    valid = jnp.asarray(valid_np.astype(jnp.int32))
    qf = q.transpose(0, 2, 1, 3).reshape(b, kh, g, sq, d).reshape(-1, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(-1, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(-1, sk, d)
    out = block_sparse_attention_kernel(qf, kf, vf, idx, valid, block=bs,
                                        interpret=interpret)
    return (out.reshape(b, kh, g, sq, d).reshape(b, h, sq, d)
            .transpose(0, 2, 1, 3))
