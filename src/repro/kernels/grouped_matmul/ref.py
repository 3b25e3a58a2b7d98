"""Pure-jnp oracle for the grouped matmul kernel: the held groups' rows
times their matrix of one layer, every other row zero."""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["grouped_matmul_ref"]


def grouped_matmul_ref(lhs, rhs, group_sizes, group_offset, layer,
                       out_dtype=None):
    """Same arguments as :func:`~repro.kernels.grouped_matmul.kernel.
    grouped_matmul`; rows not held are zero here."""
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(lhs.shape[0]), side="right")
    out = jnp.zeros((lhs.shape[0], rhs.shape[3]), jnp.float32)
    for j in range(rhs.shape[1]):
        mine = (group == group_offset + j)[:, None]
        out = jnp.where(mine, jnp.dot(lhs, rhs[layer, j],
                                      preferred_element_type=jnp.float32),
                        out)
    return out.astype(out_dtype or lhs.dtype)
