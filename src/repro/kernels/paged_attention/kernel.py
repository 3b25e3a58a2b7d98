"""Pallas TPU kernel: paged decode attention over block-table K/V pools.

The serving cache (:class:`repro.models.attention.PagedKVCache`) keeps
K/V rows in fixed-size pages of a shared pool, indirected per batch
slot through a block table.  The pure-JAX decode path resolves that
indirection by *materializing* the whole contiguous logical view every
step (``paged_kv_view``: a ``cache_len``-row gather per layer per
step) — exactly the avoidable off-chip traffic the RTC paper's
access-management argument targets.  This kernel consumes the block
table directly:

* **pool layout** — a pool is ``[layers, n_pages, page_size,
  kv_heads*head_dim]``: page-major, with all KV heads of a row side by
  side on the minor axis.  A minor axis of ``head_dim`` (64 for qwen)
  is under the TPU's 128 lanes, so the chip's default layout for a
  ``[.., page_size, kv_heads, head_dim]`` pool puts the *page* axis
  minor-most; every page-granular access then strides the whole pool,
  and a kernel that needs row-major pages forces a relayout copy of
  the pool around each call.  ``kv_heads*head_dim`` (1024 for qwen)
  fills whole lanes, so the default layout is row-major, a page is
  one contiguous block, and the decode step's row writes and this
  kernel's reads touch the pool in place;
* ``grid = (batch, n_logical_pages)`` with the page axis innermost:
  TPU grids execute sequentially over the last dimension, so the
  online-softmax running state (max, sum, accumulator — one row per
  query head) lives in VMEM scratch across the pages of one slot's
  walk;
* one grid step takes one whole pool page of one layer, all KV heads:
  the K/V block is ``(None, None, page_size, kv_heads*head_dim)``,
  whose last two dims are the pool's own, which is what the TPU
  lowering requires of a block;
* the block table, the per-slot positions and the layer index ride in
  as **scalar prefetch**
  (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`): the
  K/V BlockSpec index maps read ``(layer[0], block[b, j])`` to DMA
  exactly one pool page HBM->VMEM per grid step — the gather never
  exists, and the stacked pool of every layer is read where it lies
  (a single-layer pool is the ``layers == 1`` case);
* q and out ride lane-dense as ``(group, kv_heads*head_dim)`` blocks.
  The page is never split into heads: the query is laid out
  block-diagonally in VMEM (row ``kv*group + gi`` holds query head
  ``(kv, gi)`` on KV head ``kv``'s lanes, zeros elsewhere), so one
  matmul ``[kv_heads*group, kv_heads*head_dim] @ [.., page_size]``
  scores every query head against its own KV head's keys only, and
  ``P @ V`` masked to the same lanes accumulates each head's output
  on its own lanes.  The zeros add nothing to any sum;
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

VMEM per step: q tile (g*F*4) + K/V pages (2*page_size*F*bytes,
double-buffered) + scores (h*page_size*4) + scratch (h*(F+2)*4), with
F = kv_heads*head_dim and h = kv_heads*group query heads — the page
size is the streaming quantum.
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


def _kernel(block_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *,
            kv_heads: int, page_size: int, cache_len: int, n_lp: int,
            window: Optional[int], softcap: Optional[float]):
    del layer_ref                     # used by the K/V index maps only
    ib = pl.program_id(0)
    ij = pl.program_id(1)
    g, f = q_ref.shape
    hd = f // kv_heads
    h = kv_heads * g

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Validity of this page's rows, from the slot position alone: logical
    # slot ls holds absolute position pos - ((pos%L - ls) % L), written
    # without a vector modulo as base + ls - (ls > cur) * L.  Negative
    # means never written (ZERO page reads land here); ls >= cache_len is
    # the partial tail page's padding.  Every query head shares the mask.
    pos = pos_ref[ib]
    cur = pos % cache_len
    ls = ij * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (h, page_size), 1)
    kv_pos = pos - cur + ls - jnp.where(ls > cur, cache_len, 0)
    valid = (ls < cache_len) & (kv_pos >= 0)
    if window is not None:
        valid &= kv_pos > pos - window

    @pl.when(jnp.any(valid))
    def _step():
        # query head (kv, gi) sits on row kv*g + gi, owning KV head kv's lanes
        own = (jax.lax.broadcasted_iota(jnp.int32, (h, f), 1) // hd
               == jax.lax.broadcasted_iota(jnp.int32, (h, f), 0) // g)
        # scores in the operands' common dtype with fp32 accumulation:
        # products of two bf16 values are exact in fp32, so this is the
        # fp32 result at one MXU pass
        dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        q = q_ref[...].astype(jnp.float32)                      # [g, F]
        qb = jnp.concatenate([q] * kv_heads, axis=0) if g > 1 \
            else jnp.broadcast_to(q, (h, f))
        qb = jnp.where(own, qb, 0.0).astype(dt)                 # [h, F]
        k = k_ref[...].astype(dt)                               # [page, F]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            qb, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (hd ** -0.5)  # [h, page]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...]                                     # [h, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)  # [h, F]
        acc_ref[...] = acc_ref[...] * alpha + jnp.where(own, pv, 0.0)

    @pl.when(ij == n_lp - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)        # [h, F]
        # each query head's output is on its own lanes, zeros elsewhere:
        # summing the kv_heads row blocks folds them into [g, F]
        o = out[0:g]
        for kv in range(1, kv_heads):
            o = o + out[kv * g:(kv + 1) * g]
        o_ref[...] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("cache_len", "window", "softcap"),
)
def paged_decode_attention(
    q: jnp.ndarray,        # [b, kv_heads, group, head_dim] post-RoPE query
    kp: jnp.ndarray,       # [(layers,) n_pages, page_size, kv_heads*head_dim]
    vp: jnp.ndarray,
    block: jnp.ndarray,    # [b, n_logical_pages] int32 pool page ids
    pos: jnp.ndarray,      # [b] int32 absolute position being decoded
    layer=None,            # [] int32 layer of a stacked pool; None if 3-D
    *,
    cache_len: int,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jnp.ndarray:
    """One-token GQA attention reading K/V pages in place.

    ``kp``/``vp`` are either one layer's pool ``[n_pages, page_size,
    F]`` or the pools of every layer stacked ``[layers, n_pages,
    page_size, F]`` with ``layer`` picking one (F = kv_heads*head_dim);
    the stacked pool is read where it lies, never sliced.

    Returns [b, kv_heads, group, head_dim] — the same layout the gather
    path's grouped einsum produces before the head reshape.  Dead batch
    slots (block tables pointing at the DUMP page) return garbage rows
    exactly as the gather path does; the engine ignores them.
    """
    b, kvh, g, hd = q.shape
    f = kvh * hd
    if kp.ndim == 3:                  # one layer: the layers == 1 stack
        if layer is not None:
            raise ValueError("layer given for a single-layer pool")
        kp, vp, layer = kp[None], vp[None], 0
    elif layer is None:
        raise ValueError(f"stacked pool {kp.shape} needs a layer index")
    if kp.shape[-1] != f:
        raise ValueError(
            f"pool minor axis {kp.shape[-1]} != kv_heads*head_dim {f}")
    n_lp = block.shape[1]
    page_size = kp.shape[2]
    if n_lp * page_size < cache_len:
        raise ValueError(
            f"block table covers {n_lp} pages x {page_size} rows "
            f"< cache_len {cache_len}")
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # lane-dense q: [b, kvh, g, hd] -> [b, g, kvh*hd]
    qd = jnp.swapaxes(q, 1, 2).reshape(b, g, f)
    h = kvh * g
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_lp),
        in_specs=[
            pl.BlockSpec((None, g, f),
                         lambda ib, ij, blk, ps, ly: (ib, 0, 0)),
            # THE point of the kernel: the index map resolves the block
            # table, so each grid step DMAs exactly one pool page of one
            # layer, contiguous in the lane-dense page-major pool.
            pl.BlockSpec((None, None, page_size, f),
                         lambda ib, ij, blk, ps, ly:
                         (ly[0], blk[ib, ij], 0, 0)),
            pl.BlockSpec((None, None, page_size, f),
                         lambda ib, ij, blk, ps, ly:
                         (ly[0], blk[ib, ij], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, g, f),
                               lambda ib, ij, blk, ps, ly: (ib, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),      # running max per head
            pltpu.VMEM((h, 1), jnp.float32),      # running sum per head
            pltpu.VMEM((h, f), jnp.float32),      # output accumulator
        ],
    )
    kern = functools.partial(
        _kernel, kv_heads=kvh, page_size=page_size, cache_len=cache_len,
        n_lp=n_lp, window=window, softcap=softcap)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, f), q.dtype),
        interpret=pallas_interpret(),
    )(block, pos, layer, qd, kp, vp)
    return jnp.swapaxes(out.reshape(b, g, kvh, hd), 1, 2)
