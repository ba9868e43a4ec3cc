"""Oracle and CPU path: the grouped matmul one group at a time."""
import jax.numpy as jnp


def gmm_ref(lhs, rhs, group_sizes):
    """Rows ``[o_g, o_g + group_sizes[g])`` of ``lhs`` (m, k) times
    ``rhs[g]`` (k, n), ``o_g`` the sizes before g; rows past the last group
    come out zero."""
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), lhs.dtype)
    for g in range(rhs.shape[0]):
        out = out + jnp.where((group == g)[:, None], lhs @ rhs[g], 0)
    return out
