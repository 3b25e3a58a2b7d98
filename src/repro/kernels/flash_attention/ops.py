"""Public op: flash attention with backend dispatch.

``attention(..., backend="pallas")`` runs the tiled kernel (interpreted
off the TPU — :func:`repro.kernels.pallas_interpret`); ``backend="ref"``
runs the O(s^2) jnp oracle.  The model layer (repro.models.attention)
uses its own blocked-jnp path and never calls this op: the TPU lowering
still refuses the kernel's one-head ``(…, 1, head_dim)`` blocks.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.analysis.costs import KernelCost, register_pallas_cost
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import mha_ref

__all__ = ["attention"]


def _pallas_cost(eqn) -> KernelCost:
    """HBM bytes of one flash launch (operands ``(q, k, v)``).

    Q tiles and the output stream once (their index maps ignore the
    inner kv axis); K/V tiles are re-DMA'd for every (head, q-block)
    pair the grid sweeps — ``n_heads/n_kv_heads * n_q_blocks`` full
    passes over the KV sequence, read from the grid in the equation's
    ``grid_mapping`` so the count tracks the kernel's actual tiling.
    """
    q, k, v = eqn.invars
    grid = tuple(eqn.params["grid_mapping"].grid)   # (b, h, n_q, n_kv)
    n_q = int(grid[2])
    h = q.aval.shape[2]
    kvh = k.aval.shape[2]

    def nbytes(var):
        return int(var.aval.size) * int(var.aval.dtype.itemsize)

    kv_sweeps = (h // kvh) * n_q
    return KernelCost(
        reads=(nbytes(q), nbytes(k) * kv_sweeps, nbytes(v) * kv_sweeps),
        writes=tuple(nbytes(o) for o in eqn.outvars))


register_pallas_cost("kernels/flash_attention/", _pallas_cost)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    backend: str = "ref",
) -> jnp.ndarray:
    if backend == "ref":
        return mha_ref(q, k, v, causal=causal, window=window,
                       softcap=softcap)
    if backend == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap)
    raise ValueError(f"unknown backend {backend!r}")
