"""Faults planted in the expert layer's timed path, in ``lib/faults.py``'s
style, for the checks of the latent-attention MoE cell.  None of them
touches the layer's counters:

- ``experts_shifted``: the grouped matmul multiplies each expert's rows by
  the next held expert's weights (an off-by-one in the group order);
- ``rows_dropped``: only the first quarter of a call's tokens reach the
  grouped matmul, and the held pairs of the rest are silently not
  computed (a capacity cut);
- ``expert_dropped``: the first held expert's rows are multiplied by zero
  weights, so its routed part is silently left out.

Each is a context manager that patches the program's module attribute the
layer looks up when it is traced, and restores it after."""
from __future__ import annotations

import contextlib

FAULTS = ("experts_shifted", "rows_dropped", "expert_dropped")


def _experts_shifted(orig):
    import jax.numpy as jnp

    def broken(lhs, rhs, group_sizes, **kw):
        return orig(lhs, jnp.roll(rhs, 1, axis=0), group_sizes, **kw)
    return broken


def _expert_dropped(orig):
    def broken(lhs, rhs, group_sizes, **kw):
        return orig(lhs, rhs.at[0].set(0), group_sizes, **kw)
    return broken


def _rows_dropped(orig):
    import jax.numpy as jnp

    def broken(xt, w, idx, params, cfg, act):
        keep = jnp.arange(xt.shape[0]) < max(xt.shape[0] // 4, 1)
        return orig(xt, w, jnp.where(keep[:, None], idx, -1), params, cfg,
                    act)
    return broken


@contextlib.contextmanager
def plant(fault: str):
    import repro.models.moe as moe
    name, wrap = {"experts_shifted": ("moe_gmm", _experts_shifted),
                  "expert_dropped": ("moe_gmm", _expert_dropped),
                  "rows_dropped": ("_held_rows", _rows_dropped)}[fault]
    orig = getattr(moe, name)
    setattr(moe, name, wrap(orig))
    try:
        yield
    finally:
        setattr(moe, name, orig)
