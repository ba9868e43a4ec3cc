"""Public wrapper: model-layout flash-decode."""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attn.kernel import decode_attention_kernel


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     bk: int = 128, interpret: bool | None = None):
    """q: (B, 1, H, hd); caches: (B, Sc, K, hd); pos scalar → (B, 1, H, hd)."""
    b, _, h, d = q.shape
    _, sc, kh, _ = k_cache.shape
    g = h // kh
    qf = q.transpose(0, 2, 1, 3).reshape(b, kh, g, 1, d).reshape(-1, 1, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(-1, sc, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(-1, sc, d)
    out = decode_attention_kernel(qf, kf, vf, pos, window=window, bk=bk,
                                  interpret=interpret)
    return (out.reshape(b, kh, g, 1, d).reshape(b, h, 1, d)
            .transpose(0, 2, 1, 3))
