"""Public wrapper for the fused LoRA projection (PFTT serving hot path).

This is the serving lowering of the factored LoRA contract: model code
reaches it through ``peft.lora_proj(..., backend="pallas")`` (threaded via
``Model.*(opts={"lora_backend": "pallas"})``), computing the unmerged form
``x·W + scale·(x·A)·B`` in one fused pass.  Forward-only — ``pallas_call``
has no VJP here, so training keeps the jnp factored path.  A dim larger
than its block must split into tiling-aligned blocks, else the kernel
raises (``kernel._block``)."""
from __future__ import annotations

import functools

import jax

from repro.kernels.lora_fused.kernel import lora_fused_kernel


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def lora_matmul(x, w, a, b, *, scale: float, interpret: bool | None = None):
    """x: (..., K) @ [W (K,N) + scale·A(K,r)·B(r,N)] → (..., N)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    xf = x.reshape(-1, k)
    out = lora_fused_kernel(xf, w, a, b, scale=scale, interpret=interpret)
    return out.reshape(*lead, w.shape[1])
