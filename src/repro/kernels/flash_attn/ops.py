"""Public wrapper: model-layout (B,S,H,hd) GQA attention via the Pallas
flash kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, interpret: bool | None = None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd) → (B, Sq, H, hd).

    Flattens (batch, head) into the kernel's leading grid dim; GQA sharing is
    resolved inside the kernel's kv index map (no broadcast materialized)."""
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    g = h // kh
    # (B, S, H, d) → (B, K, G, S, d) → (B·K·G, S, d): head-major so that
    # bh // g indexes the right kv head
    qf = q.transpose(0, 2, 1, 3).reshape(b, kh, g, sq, d).reshape(-1, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(-1, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(-1, sk, d)
    out = flash_attention_kernel(qf, kf, vf, causal=causal, window=window,
                                 bq=bq, bk=bk, interpret=interpret)
    return (out.reshape(b, kh, g, sq, d).reshape(b, h, sq, d)
            .transpose(0, 2, 1, 3))
