"""Mamba-2 SSD chunked-scan Pallas kernel (TPU target).

Grid: (B·H, n_chunks).  The chunk dimension iterates sequentially per (b,h),
carrying the SSM state (P×N) in VMEM scratch — the inter-chunk recurrence
lives *inside* the kernel, so a layer's whole scan is one pallas_call.  The
intra-chunk term is the masked (L×L)·(L×P) GEMM pair the MXU wants; chunk
length L=128…256 keeps q/k-like operands and the state in VMEM.

Inputs are pre-projected (x, dt, B, C per head); gating/conv/projections
stay in XLA (they are plain GEMMs it already fuses well).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hlast_ref, state_ref,
            *, chunk: int, n_chunks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0].astype(jnp.float32)        # (L, 1) → (L,)
    dt = dt[:, 0]
    a_coef = a_ref[0, 0]                      # scalar
    bmat = b_ref[0].astype(jnp.float32)       # (L, N)
    cmat = c_ref[0].astype(jnp.float32)       # (L, N)

    ad = dt * a_coef                          # (L,)
    cs = jnp.cumsum(ad)                       # (L,)
    # intra-chunk decay matrix: exp(cs_i - cs_j) for i >= j else 0
    diff = cs[:, None] - cs[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(li >= lj, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())))
    scores = scores * decay * dt[None, :]
    y_intra = jax.lax.dot(scores, x)          # (L, P)

    state = state_ref[...]                    # (P, N)
    y_inter = jax.lax.dot(cmat * jnp.exp(cs)[:, None], state.T)  # (L, P)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    total = cs[-1]
    decay_out = jnp.exp(total - cs)           # (L,)
    contrib = bmat * (dt * decay_out)[:, None]          # (L, N)
    state_ref[...] = (jnp.exp(total) * state
                      + jax.lax.dot(x.T, contrib))      # (P, N)

    @pl.when(j == n_chunks - 1)
    def _emit_state():
        hlast_ref[0] = state_ref[...]


def ssd_chunk_kernel(x, dt, a_coef, bmat, cmat, *, chunk: int,
                     interpret: bool | None = None):
    """x: (BH, S, P); dt: (BH, S); a_coef: (BH,); b/c: (BH, S, N)
    → (y (BH, S, P), h_final (BH, P, N))."""
    bh, s, p = x.shape
    n = bmat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=nc)
    y, hlast = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, 1), lambda b, j: (b, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, p, n), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(x, dt[..., None], a_coef[:, None], bmat, cmat)
    return y, hlast
