"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package has:
* ``kernel.py`` — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
* ``ops.py``    — jit'd public wrapper (layout handling, GQA broadcast, ...)
* ``ref.py``    — pure-jnp oracle used by the allclose sweep tests

Whether a kernel is interpreted is decided here, from the backend: the
Pallas interpreter runs on the CPU backend (tests), and every other backend
compiles the kernel.  An explicit ``interpret=`` argument overrides it.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` if given, else True only on the CPU backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
