"""Public op: paged decode attention with backend dispatch.

``paged_attention(..., backend="pallas")`` runs the block-table Pallas
kernel (compiled on a TPU, interpreted elsewhere —
:func:`repro.kernels.pallas_interpret`); ``backend="ref"`` runs the gather +
dense-softmax jnp oracle.  The model layer
(``repro.models.attention.attn_decode``) calls this op when the serving
engine selects ``decode_backend="pallas_paged"``; the oracle is the
parity anchor for the kernel test sweep.

Mesh locality: the kernel itself is mesh-oblivious — it indexes whatever
pool it is handed via the block table.  On multi-device meshes the
serving engine wraps the decode step in ``shard_map``
(:func:`repro.serve.engine.build_decode_step`): each device's program
receives its *local* pool extent plus the block-table rows of the slots
pinned to that shard, with global page ids rebased to local ones by
partition-id arithmetic before the call.  The kernel therefore never
causes a GSPMD gather, and :func:`_pallas_cost` — which prices a launch
from its operand avals — automatically bills the per-shard shapes that
the analysis walker multiplies by the shard count for the exact global
HBM figure.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.analysis.costs import KernelCost, register_pallas_cost
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_decode_ref

__all__ = ["paged_attention"]


def _pallas_cost(eqn) -> KernelCost:
    """HBM bytes of one kernel launch, from the equation's operand avals.

    Operand order is fixed by ``kernel.py``'s pallas_call: ``(block,
    pos, layer, q, kp, vp)``, with ``kp``/``vp`` the stacked
    ``[layers, n_pages, page_size, kv_heads*head_dim]`` pools.  The
    scalar-prefetch operands (block, pos, layer) and q (index map
    depends only on outer grid axes) stream once.  K and V are billed
    for the full walk, every logical page's physical page of the one
    layer DMA'd whole, all KV heads at once — exactly
    ``TrafficModel.kv_page_read_bytes`` at full occupancy.  That is an
    upper bound: the kernel copies only the pages that hold a valid
    row, which the avals cannot tell.  The output block is written
    once per batch slot.
    """
    block, pos, layer, q, kp, vp = eqn.invars
    b, n_lp = block.aval.shape
    page, f = kp.aval.shape[-2:]
    page_read = b * n_lp * page * f * int(kp.aval.dtype.itemsize)

    def nbytes(v):
        return int(v.aval.size) * int(v.aval.dtype.itemsize)

    return KernelCost(
        reads=(nbytes(block), nbytes(pos), nbytes(layer), nbytes(q),
               page_read, page_read),
        writes=tuple(nbytes(v) for v in eqn.outvars))


register_pallas_cost("kernels/paged_attention/", _pallas_cost)


def paged_attention(
    q: jnp.ndarray,        # [b, kv_heads, group, head_dim]
    kp: jnp.ndarray,       # [(layers,) n_pages, page_size, kv_heads*head_dim]
    vp: jnp.ndarray,
    block: jnp.ndarray,    # [b, n_logical_pages] int32
    pos: jnp.ndarray,      # [b] int32
    layer=None,            # [] int32 layer of a stacked pool; None if 3-D
    *,
    cache_len: int,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    backend: str = "pallas",
) -> jnp.ndarray:
    if backend == "ref":
        return paged_decode_ref(q, kp, vp, block, pos, layer,
                                cache_len=cache_len, window=window,
                                softcap=softcap)
    if backend == "pallas":
        return paged_decode_attention(q, kp, vp, block, pos, layer,
                                      cache_len=cache_len, window=window,
                                      softcap=softcap)
    raise ValueError(f"unknown backend {backend!r}")
