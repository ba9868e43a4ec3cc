"""Grouped expert matmul for the held experts of an MoE layer.

``moe_gmm(lhs, rhs, group_sizes)``: ``lhs`` (m, k) holds the rows routed to
each expert in turn (``group_sizes[g]`` rows for expert g, sorted by
expert), ``rhs`` (G, k, n) the experts' weights.  On the TPU it is
megablox's grouped matmul (``jax.experimental.pallas.ops.tpu.megablox``),
whose grid visits only the row tiles that hold a group's rows: rows past
``sum(group_sizes)`` are never multiplied, and their output rows are left
unwritten, so callers mask them.  On the CPU backend it is the plain
per-group path (``ref.gmm_ref``, zeros past the groups); ``interpret=True``
runs the megablox kernel in the Pallas interpreter instead (tests).
``m`` must be a multiple of 128 on the kernel paths.  Differentiable on
every path (megablox carries its own VJP)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.ref import gmm_ref


def tiling(m: int, k: int, n: int):
    """(tm, tk, tn): row tiles of 256 where ``m`` allows, else 128; the
    contraction and output dims in blocks of 512 where they divide by
    512, else whole (a block equal to the full dim is always legal)."""
    tm = 256 if m % 256 == 0 else 128
    return tm, (512 if k % 512 == 0 else k), (512 if n % 512 == 0 else n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(lhs, rhs, group_sizes, *, interpret: bool | None = None):
    group_sizes = group_sizes.astype(jnp.int32)
    if interpret is None and jax.default_backend() == "cpu":
        return gmm_ref(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = lhs.shape
    return gmm(lhs, rhs, group_sizes, lhs.dtype,
               tiling(m, k, rhs.shape[2]), None, None, False,
               bool(interpret))
