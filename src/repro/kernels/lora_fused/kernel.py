"""Fused LoRA matmul Pallas kernel: y = x·W + (α/r)·(x·A)·B in one pass.

PFTT serves *unmerged* personalized models (base W stays shared across
clients; each client's LoRA is tiny).  Fusing the low-rank path into the
base GEMM avoids a second read of x from HBM and keeps the (bm × r)
intermediate in VMEM — the arithmetic intensity of the LoRA path alone is
far below the TPU ridge point, so unfused it is pure memory traffic.

Grid: (M/bm, N/bn, K/bk); accumulators for both the base tile and the x·A
tile live in VMEM scratch across the K iteration; the rank-r correction is
applied on the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, xa_ref, *,
            scale: float, n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    acc_ref[...] += jax.lax.dot(x, w_ref[...],
                                preferred_element_type=jnp.float32)
    xa_ref[...] += jax.lax.dot(x, a_ref[...],
                               preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _finalize():
        lora = jax.lax.dot(xa_ref[...].astype(b_ref.dtype), b_ref[...],
                           preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * lora).astype(o_ref.dtype)


def _block(dim: int, pref: int, align: int) -> int:
    """Block size along one dim: the whole dim when it fits in ``pref``,
    else the largest multiple of ``align`` (the TPU tiling: 8 f32 / 16 bf16
    rows, or 128 lanes) ≤ ``pref`` that tiles ``dim`` exactly.  Any other block is
    refused by the chip's compiler, so a dim with none raises."""
    if dim <= pref:
        return dim
    for cand in range(pref - pref % align, 0, -align):
        if dim % cand == 0:
            return cand
    raise ValueError(f"lora_fused: dim {dim} > {pref} has no block that is "
                     f"a multiple of {align} and divides it")


def lora_fused_kernel(x, w, a, b, *, scale: float, bm: int = 128,
                      bn: int = 128, bk: int = 128,
                      interpret: bool | None = None):
    """x: (M, K); w: (K, N); a: (K, r); b: (r, N) → (M, N)."""
    m, k = x.shape
    _, n = w.shape
    r = a.shape[1]
    rows = max(8, 32 // x.dtype.itemsize)
    bm, bn, bk = _block(m, bm, rows), _block(n, bn, 128), _block(k, bk, 128)
    nm, nn, nk = m // bm, n // bn, k // bk

    kernel = functools.partial(_kernel, scale=scale, n_k=nk)
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, r), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((r, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(x, w, a, b)
