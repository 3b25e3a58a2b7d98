"""Pallas TPU kernel: paged decode attention over block-table K/V pools.

The serving cache (:class:`repro.models.attention.PagedKVCache`) keeps
K/V rows in fixed-size pages of a shared pool, indirected per batch
slot through a block table.  The pure-JAX decode path resolves that
indirection by *materializing* the whole contiguous logical view every
step (``paged_kv_view``: a ``cache_len``-row gather per layer per
step) — exactly the avoidable off-chip traffic the RTC paper's
access-management argument targets.  This kernel consumes the block
table directly:

* ``grid = (batch, n_logical_pages)`` with the page axis innermost:
  TPU grids execute sequentially over the last dimension, so the
  online-softmax running state (max, sum, accumulator — one row per
  query head) lives in VMEM scratch across the pages of one slot's
  walk;
* one grid step takes one whole pool page, all KV heads: the K/V block
  is ``(1, page_size, kv_heads, head_dim)``, whose last two dims are
  the pool's own, which is what the TPU lowering requires of a block
  (a one-head block ``(…, 1, head_dim)`` is refused);
* the block table and per-slot positions ride in as **scalar
  prefetch** (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`):
  the K/V BlockSpec index maps read ``block[b, j]`` to DMA exactly one
  pool page HBM->VMEM per grid step — the gather never exists, pages
  stream through on-chip memory in block-table order;
* q and out ride as ``(1, kv_heads, group, head_dim)`` blocks; the
  page's ``[page_size, kv_heads, head_dim]`` rows are swapped to
  head-major in VMEM and scored by one matmul batched over KV heads, so
  each query head meets only its own GQA head's keys (no cross-head
  work) and the running state is kept per head;
* ring/append semantics, sliding windows, and softcap are enforced
  in-kernel from ``pos`` alone: logical slot ``s`` holds absolute
  position ``pos - ((pos % cache_len - s) % cache_len)`` (negative =
  never written), matching ``attention._cache_positions``; the partial
  tail page (``cache_len % page_size != 0``) masks its out-of-range
  rows the same way;
* pages with no valid row (unwritten ZERO pages, fully out-of-window
  pages) take a block-level early exit — no MXU cycles, mirroring the
  banded FLOP count of the jnp path;
* fp32 accumulation; one query token per slot (decode).

VMEM per step: q tile (h*hd*4) + K/V pages (2*page_size*kv_heads*hd*
bytes, double-buffered) + scores (h*page_size*4) + scratch
(h*(hd+2)*4), with h = kv_heads * group query heads — the page size is
the streaming quantum.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

__all__ = ["paged_decode_attention"]

_NEG_INF = -1e30


def _kernel(block_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            page_size: int, cache_len: int, n_lp: int,
            window: Optional[int], softcap: Optional[float]):
    ib = pl.program_id(0)
    ij = pl.program_id(1)
    g, hd = q_ref.shape[2], q_ref.shape[3]

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Validity of this page's rows, from the slot position alone: logical
    # slot ls holds absolute position pos - ((pos%L - ls) % L), written
    # without a vector modulo as base + ls - (ls > cur) * L.  Negative
    # means never written (ZERO page reads land here); ls >= cache_len is
    # the partial tail page's padding.  Every KV head shares the mask.
    pos = pos_ref[ib]
    cur = pos % cache_len
    ls = ij * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (g, page_size), 1)
    kv_pos = pos - cur + ls - jnp.where(ls > cur, cache_len, 0)
    valid = (ls < cache_len) & (kv_pos >= 0)
    if window is not None:
        valid &= kv_pos > pos - window

    @pl.when(jnp.any(valid))
    def _step():
        q = q_ref[0].astype(jnp.float32)                        # [kvh, g, hd]
        # [page, kvh, hd] -> [kvh, page, hd]: one batched matmul per
        # page, batched over KV heads, scores each head's own rows only
        k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)
        v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)
        s = jnp.einsum("hgd,hpd->hgp", q, k,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = jnp.broadcast_to(valid[None], s.shape)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                                     # [kvh, g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hgp,hpd->hgd", p, v, preferred_element_type=jnp.float32)

    @pl.when(ij == n_lp - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cache_len", "window", "softcap"),
)
def paged_decode_attention(
    q: jnp.ndarray,        # [b, kv_heads, group, head_dim] post-RoPE query
    kp: jnp.ndarray,       # [n_pages, page_size, kv_heads, head_dim] pool
    vp: jnp.ndarray,
    block: jnp.ndarray,    # [b, n_logical_pages] int32 pool page ids
    pos: jnp.ndarray,      # [b] int32 absolute position being decoded
    *,
    cache_len: int,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jnp.ndarray:
    """One-token GQA attention reading K/V pages in place.

    Returns [b, kv_heads, group, head_dim] — the same layout the gather
    path's grouped einsum produces before the head reshape.  Dead batch
    slots (block tables pointing at the DUMP page) return garbage rows
    exactly as the gather path does; the engine ignores them.
    """
    b, kvh, g, hd = q.shape
    n_lp = block.shape[1]
    page_size = kp.shape[1]
    if n_lp * page_size < cache_len:
        raise ValueError(
            f"block table covers {n_lp} pages x {page_size} rows "
            f"< cache_len {cache_len}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_lp),
        in_specs=[
            pl.BlockSpec((1, kvh, g, hd),
                         lambda ib, ij, blk, ps: (ib, 0, 0, 0)),
            # THE point of the kernel: the index map resolves the block
            # table, so each grid step DMAs exactly one pool page.
            pl.BlockSpec((1, page_size, kvh, hd),
                         lambda ib, ij, blk, ps: (blk[ib, ij], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, hd),
                         lambda ib, ij, blk, ps: (blk[ib, ij], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvh, g, hd),
                               lambda ib, ij, blk, ps: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, 1), jnp.float32),     # running max per head
            pltpu.VMEM((kvh, g, 1), jnp.float32),     # running sum per head
            pltpu.VMEM((kvh, g, hd), jnp.float32),    # output accumulator
        ],
    )
    kern = functools.partial(
        _kernel, page_size=page_size, cache_len=cache_len, n_lp=n_lp,
        window=window, softcap=softcap)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=pallas_interpret(),
    )(block, pos, q, kp, vp)
