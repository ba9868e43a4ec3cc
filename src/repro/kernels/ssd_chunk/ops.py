"""Public wrapper: model-layout SSD scan via the Pallas chunk kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_chunk.kernel import ssd_chunk_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_coef, bmat, cmat, *, chunk: int = 256,
             interpret: bool | None = None):
    """Model layout: x (B,S,H,P); dt (B,S,H); a_coef (H,); b/c (B,S,H,N)
    → (y (B,S,H,P), h_final (B,H,P,N))."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s)
    af = jnp.tile(a_coef, b)
    bf = bmat.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    cf = cmat.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    y, hf = ssd_chunk_kernel(xf, dtf, af, bf, cf, chunk=chunk,
                             interpret=interpret)
    return (y.reshape(b, h, s, p).transpose(0, 2, 1, 3),
            hf.reshape(b, h, p, n))
