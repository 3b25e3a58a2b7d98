"""Public op: grouped matmul with backend dispatch (``"pallas"``: the
kernel, compiled on a TPU and interpreted elsewhere; ``"ref"``: the jnp
oracle, which zeroes the rows not held)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.analysis.costs import KernelCost, register_pallas_cost
from repro.kernels.grouped_matmul.kernel import grouped_matmul as _kernel
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref

__all__ = ["grouped_matmul"]


def _pallas_cost(eqn) -> KernelCost:
    """HBM bytes of one launch, from the operand avals: the group
    metadata and lhs once, one layer of the held groups' weights once
    (the kernel keeps a group's weight block across its row tiles), the
    output once.  The lhs is read again for each n tile after the first:
    a lower bound, exact where one n tile spans the output, as in a
    decode step the weights outweigh.  Operand order is ``kernel.py``'s:
    ``(offsets, group_ids, tile_ids, group_offset, layer, lhs, rhs)``."""
    def nbytes(v):
        return int(v.aval.size) * int(v.aval.dtype.itemsize)

    *meta, lhs, rhs = eqn.invars
    layers = int(rhs.aval.shape[0])
    return KernelCost(
        reads=tuple(nbytes(v) for v in meta) + (nbytes(lhs),
                                                nbytes(rhs) // layers),
        writes=tuple(nbytes(v) for v in eqn.outvars))


register_pallas_cost("kernels/grouped_matmul/", _pallas_cost)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, group_offset=0, layer=None,
                   out_dtype=None, backend: str = "pallas") -> jnp.ndarray:
    """``rhs``: one layer's ``[held, k, n]`` (``layer`` None) or the
    stack ``[layers, held, k, n]`` read at ``layer``."""
    if layer is None:
        rhs, layer = rhs[None], 0
    if backend == "pallas":
        return _kernel(lhs, rhs, group_sizes, group_offset, layer,
                       out_dtype=out_dtype)
    if backend == "ref":
        return grouped_matmul_ref(lhs, rhs, group_sizes, group_offset,
                                  layer, out_dtype)
    raise ValueError(f"unknown backend {backend!r}")
