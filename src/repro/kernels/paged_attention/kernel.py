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
* **block walk** — ``grid = (batch, n_blocks)``, a block being ``P``
  consecutive logical pages of one slot, with the block axis innermost:
  TPU grids execute sequentially, so the online-softmax running state
  (max, sum, accumulator — one row per query head) lives in VMEM
  scratch across the blocks of one slot's walk.  A grid step costs a
  fixed time whatever it moves (about 0.35 us on a v5e), so a step
  takes ``P`` pages, not one;
* **manual DMA** — the K/V pools stay in HBM (``memory_space=ANY``)
  and each page of a block that holds a valid row is copied by its own
  DMA into a double-buffered VMEM buffer ``[2, P, page_size, F]``,
  reading the pool page id from the block table and the layer from
  scalar prefetch: the gather never exists, and the stacked pool of
  every layer is read where it lies (a single-layer pool is the
  ``layers == 1`` case).  Pages with no valid row are never fetched,
  and a block with none costs no DMA and no vector work;
* **prefetch** — each grid step first starts the copies of the *next*
  grid step's live pages into the other buffer (the next step may be
  the first block of the next slot), then waits on its own, which the
  step before started; the first step starts its own.  So a live
  block's copies run under the previous step's work;
* **liveness, from ``pos`` alone** — logical slot ``s`` holds absolute
  position ``pos - ((pos % cache_len - s) % cache_len)`` (negative =
  never written), matching ``attention._cache_positions``: a row at ring
  distance ``d`` behind the newest is valid iff ``d < min(pos + 1,
  window, cache_len)``, and a page is live iff its nearest row is.  The
  page rule runs in scalar code; the same rule per row masks the
  scores, ring wrap, windows and the partial tail page
  (``cache_len % page_size != 0``) included;
* **stale rows** — a live block's pages that were not fetched hold
  whatever the buffer held before (an earlier block, or nothing yet).
  Their rows are invalid by the rule above, and V is masked as well as
  the scores (``where(valid, v, 0)``), so no stale NaN or Inf bit
  pattern reaches ``p @ v``;
* q and out ride lane-dense as ``(group, kv_heads*head_dim)`` blocks.
  The page is never split into heads: the query is laid out
  block-diagonally in VMEM (row ``kv*group + gi`` holds query head
  ``(kv, gi)`` on KV head ``kv``'s lanes, zeros elsewhere), so one
  matmul ``[kv_heads*group, kv_heads*head_dim] @ [.., P*page_size]``
  scores every query head against its own KV head's keys only, and
  ``P @ V`` masked to the same lanes accumulates each head's output
  on its own lanes.  The zeros add nothing to any sum;
* fp32 scores, softmax and accumulation; one query token per slot
  (decode).

``P`` (:func:`pages_per_block`) comes from the shapes alone: as many
pages as make about ``BLOCK_BYTES`` of K, capped at the block table's
width — 16 pages of 32 KiB for qwen1.5-0.5b (``F`` 1024, bf16, 16-row
pages), all 64 of 8 KiB for one chip's share of Mixtral-8x22B (``F``
256).  On a v5e at both shapes, 512 KiB beat 64-256 KiB blocks and
matched 1 MiB.  VMEM per step: K and V buffers ``2 * 2 * P *
page_size * F * itemsize`` (2 MiB at ``BLOCK_BYTES``), the fp32 V
block, the q tile, scores ``h * P * page_size * 4`` and scratch ``h *
(F + 2) * 4``, with ``F = kv_heads*head_dim`` and ``h =
kv_heads*group`` query heads.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

__all__ = ["paged_decode_attention", "pages_per_block"]

_NEG_INF = -1e30

#: bytes of K a grid step aims to fetch
BLOCK_BYTES = 512 * 2 ** 10


def pages_per_block(page_size: int, width: int, itemsize: int,
                    n_lp: int) -> int:
    """Pages a grid step takes: about ``BLOCK_BYTES`` of K pages of
    ``page_size x width`` elements of ``itemsize`` bytes, at least one
    and at most the ``n_lp`` pages of a block table row."""
    return max(1, min(n_lp, BLOCK_BYTES // (page_size * width * itemsize)))


def _kernel(block_ref, pos_ref, layer_ref, q_ref, kp_hbm, vp_hbm, o_ref,
            kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
            kv_heads: int, page_size: int, cache_len: int, ppb: int,
            window: Optional[int], softcap: Optional[float]):
    ib, ij = pl.program_id(0), pl.program_id(1)
    n_b, n_j = pl.num_programs(0), pl.num_programs(1)
    g, f = q_ref.shape
    hd = f // kv_heads
    h = kv_heads * g
    rows = ppb * page_size
    buf = (ib * n_j + ij) % 2         # this step's half of the buffers

    n_pages = -(-cache_len // page_size)   # pages that hold ring rows

    def live_runs(b, j):
        """Block j's live pages of slot b, as two runs ``[lo, hi)`` of
        page offsets in the block.  The valid rows are the ``top`` rows
        of the ring ending at the newest, ``cur``; they wrap past row 0
        when the oldest, ``first``, is negative.  Scalar code, the same
        for a dead block as for a live one."""
        pos = pos_ref[b]
        cur = jax.lax.rem(pos, cache_len)
        top = jnp.minimum(pos + 1, cache_len)
        if window is not None:
            top = jnp.minimum(top, window)
        first = cur + 1 - top
        newest = jax.lax.div(cur, page_size) + 1
        # rows [max(first, 0), cur]; if wrapped, [first + L, L) in the
        # pages after cur's (cur's page holds both ends of a full ring)
        wrap = jnp.where(first < 0, jnp.maximum(
            jax.lax.div(first + cache_len, page_size), newest), n_pages)
        runs = ((jax.lax.div(jnp.maximum(first, 0), page_size), newest),
                (wrap, n_pages))
        base = j * ppb
        return [(jnp.clip(lo - base, 0, ppb), jnp.clip(hi - base, 0, ppb))
                for lo, hi in runs]

    def page_copies(half, k, pid):
        layer = layer_ref[0]
        return (pltpu.make_async_copy(kp_hbm.at[layer, pid],
                                      kbuf.at[half, k], sems.at[0, half]),
                pltpu.make_async_copy(vp_hbm.at[layer, pid],
                                      vbuf.at[half, k], sems.at[1, half]))

    def start(b, j, half):
        for lo, hi in live_runs(b, j):
            @pl.loop(lo, hi)
            def _(k):
                for c in page_copies(half, k, block_ref[b, j * ppb + k]):
                    c.start()

    @pl.when((ib == 0) & (ij == 0))
    def _first():
        start(ib, ij, buf)

    # the next grid step's pages go into the other half before this
    # step waits on its own
    last = ij == n_j - 1
    nb = jnp.where(last, ib + 1, ib)

    @pl.when(nb < n_b)
    def _prefetch():
        start(nb, jnp.where(last, 0, ij + 1), 1 - buf)

    @pl.when(ij == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    n_live = sum(jnp.maximum(hi - lo, 0) for lo, hi in live_runs(ib, ij))

    @pl.when(n_live > 0)
    def _step():
        # the copies of one half are alike in size, so each wait is
        # described by a copy of page 0
        @pl.loop(0, n_live)
        def _(i):
            for c in page_copies(buf, 0, 0):
                c.wait()

        # validity of this block's rows, by the same rule as the pages:
        # logical slot ls holds absolute position pos - ((pos%L - ls) % L),
        # written without a vector modulo as base + ls - (ls > cur) * L.
        # Negative means never written; ls >= cache_len is the partial
        # tail page's padding, and a page of the last block past the
        # table is past cache_len too.  Every query head shares the mask.
        pos = pos_ref[ib]
        cur = pos % cache_len

        def valid_rows(shape, axis):
            ls = ij * rows + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            kv_pos = pos - cur + ls - jnp.where(ls > cur, cache_len, 0)
            ok = (ls < cache_len) & (kv_pos >= 0)
            if window is not None:
                ok &= kv_pos > pos - window
            return ok

        valid = valid_rows((h, rows), 1)
        # query head (kv, gi) sits on row kv*g + gi, owning KV head kv's lanes
        own = (jax.lax.broadcasted_iota(jnp.int32, (h, f), 1) // hd
               == jax.lax.broadcasted_iota(jnp.int32, (h, f), 0) // g)
        # scores in the operands' common dtype with fp32 accumulation:
        # products of two bf16 values are exact in fp32, so this is the
        # fp32 result at one MXU pass
        dt = jnp.promote_types(q_ref.dtype, kbuf.dtype)
        q = q_ref[...].astype(jnp.float32)                      # [g, F]
        qb = jnp.concatenate([q] * kv_heads, axis=0) if g > 1 \
            else jnp.broadcast_to(q, (h, f))
        qb = jnp.where(own, qb, 0.0).astype(dt)                 # [h, F]
        k = kbuf[buf].reshape(rows, f).astype(dt)               # [rows, F]
        s = jax.lax.dot_general(
            qb, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (hd ** -0.5)  # [h, rows]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[...]                                     # [h, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = vbuf[buf].reshape(rows, f).astype(jnp.float32)
        v = jnp.where(valid_rows((rows, 1), 0), v, 0.0)         # stale rows
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)  # [h, F]
        acc_ref[...] = acc_ref[...] * alpha + jnp.where(own, pv, 0.0)

    @pl.when(last)
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
    ppb = pages_per_block(page_size, f, kp.dtype.itemsize, n_lp)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # lane-dense q: [b, kvh, g, hd] -> [b, g, kvh*hd]
    qd = jnp.swapaxes(q, 1, 2).reshape(b, g, f)
    h = kvh * g
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, pl.cdiv(n_lp, ppb)),
        in_specs=[
            pl.BlockSpec((None, g, f),
                         lambda ib, ij, blk, ps, ly: (ib, 0, 0)),
            # K/V stay in HBM: the kernel copies each live page itself
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, g, f),
                               lambda ib, ij, blk, ps, ly: (ib, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, f), kp.dtype),  # K, two halves
            pltpu.VMEM((2, ppb, page_size, f), vp.dtype),  # V, two halves
            pltpu.SemaphoreType.DMA((2, 2)),               # [K/V, half]
            pltpu.VMEM((h, 1), jnp.float32),      # running max per head
            pltpu.VMEM((h, 1), jnp.float32),      # running sum per head
            pltpu.VMEM((h, f), jnp.float32),      # output accumulator
        ],
    )
    kern = functools.partial(
        _kernel, kv_heads=kvh, page_size=page_size, cache_len=cache_len,
        ppb=ppb, window=window, softcap=softcap)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, f), q.dtype),
        # the prefetch crosses from one slot's walk into the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
    )(block, pos, layer, qd, kp, vp)
    return jnp.swapaxes(out.reshape(b, g, kvh, hd), 1, 2)
