"""Pallas TPU kernels for the reproduction's compute hot-spots.

Each kernel is a package of three modules — ``kernel.py`` (the Pallas
TPU implementation, run in interpret mode off the TPU so CI validates
it without hardware), ``ref.py`` (a pure-jnp oracle with the same
feature set), and ``ops.py`` (the public op with ``backend="pallas" |
"ref"`` dispatch).  The kernel CI job runs every package's parity suite
in interpret mode.

Packages
--------
``flash_attention``
    Tiled online-softmax attention for training/prefill (GQA, causal,
    sliding-window, softcap).  Sequences that don't tile are padded to
    the block grid and sliced back (padded keys sit past every real
    query causally; padded query rows are discarded).
``rate_match``
    Algorithm-1 transfer-schedule bits.
``refresh_sim``
    Retention-window age update of the refresh simulator.
``paged_attention``
    Decode attention that consumes the serving cache's block tables
    *directly* — the RTC argument applied to the serving hot path.

Paged-attention design note (PR 5)
----------------------------------
The paged serving cache (:class:`repro.models.attention.PagedKVCache`)
stores K/V rows in fixed-size pages of a shared pool behind a per-slot
block table.  The pure-JAX decode path materializes the contiguous
logical view every step (``paged_kv_view``: a ``cache_len``-row gather
per attention layer), which is precisely the predictable-but-wasted
memory traffic the paper's refresh-triggered access management
eliminates — the data already sits in DRAM in a layout an address
generator can walk, so copying it into a contiguous staging buffer
buys nothing.

The kernel removes the copy:

* **Grid layout** — ``(batch_slot, page_block)`` with the block axis
  innermost; one step takes a block of ``P`` logical pages of one
  slot, each a whole pool page of one layer, all KV heads
  (``pages_per_block``: about 512 KiB of K, from the shapes alone; a
  grid step has a fixed cost on the chip, so one-page steps were the
  kernel's largest cost).  The pool row is lane-dense, every KV head
  side by side, so the query is laid out block-diagonally in VMEM and
  one matmul scores every query head against its own KV head's lanes
  only.  TPU grids are sequential, so the online-softmax state
  (running max, running sum, fp32 output accumulator, per query head)
  lives in VMEM scratch across one slot's walk.
* **Pool layout** — ``[layers, n_pages, page_size,
  kv_heads*head_dim]``.  With ``head_dim`` (64 for qwen) as the minor
  axis the TPU's default layout put the page axis minor-most: every
  page write strode the whole pool, and the decode step copied each
  layer's pool into and out of the kernel's row-major layout.  A minor
  axis of ``kv_heads*head_dim`` fills whole lanes, so the default
  layout is row-major and a page is contiguous.  The decode step
  carries the stacked pools through its layer scan and the kernel takes
  the layer as a third scalar-prefetch operand, so no pool is sliced,
  copied or restacked (guarded by ``tests/test_tpu_compile.py``).
* **Block-table DMA** — the block table, per-slot positions and the
  layer index are scalar-prefetch operands
  (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`); the
  pools stay in HBM and the kernel copies each page ``(layer,
  block[b, j])`` of a block that holds a valid row into a
  double-buffered VMEM block, starting the next grid step's copies
  before it computes on its own.  Ring/append validity, sliding
  windows, softcap, and the partial tail page are reconstructed
  in-kernel from ``pos`` alone (matching
  ``attention._cache_positions``): in scalar code for the pages to
  copy, per row for the masks, V included, so rows of pages not
  copied never reach the sums.  A block with no valid row costs no
  copy and no vector work.
* **Why no gather** — the gather costs a full logical-view read+write
  per layer per step regardless of context occupancy and defeats the
  energy model's point (telemetry now accounts that phantom traffic on
  the gather path and only true per-page reads on the kernel path).
  The kernel's traffic is ``ceil(ctx/page_size)`` pages per layer —
  the minimum the block-table indirection permits.

Engine-side selection: ``ServeEngine(decode_backend="pallas_paged")``
(default ``"gather"``); generations are identical across backends on
all 10 archs (interpret-mode parity is accumulation-order tolerant on
logits, bit-exact on sampled tokens — pinned in
``tests/test_paged_attention_kernel.py``).

Device-local decode under ``shard_map`` (PR 8)
----------------------------------------------
On a mesh, GSPMD cannot see through the block-table indirection: any
page of the shared pool might serve any slot, so partitioning the
unmapped kernel forces all-gathers of the *whole pool* every step —
the ``pool-collective`` finding family the static auditor used to
baseline.  The fix is layout, not kernel code: the kernel itself stays
mesh-oblivious (one slot's page walk never crosses a slot
boundary), and the serving layer makes locality true by construction.
:class:`~repro.serve.paging.PageTable` pins slots to data-axis shards
and carves the pool into per-shard extents (``shards`` contiguous
ranges of pages, each with its own free list and reserved zero/dump
pages), so a slot's block table only ever names pages in its own
shard's extent.  ``ServeEngine`` then wraps the decode step in
:func:`jax.shard_map` with the pool, block tables, and slot axes
sharded over ``data``: each device runs the unchanged kernel over its
local pool extent (block ids rebased by the shard's page offset
in-body), and the only cross-device traffic left is the per-step token
exchange.  Generations are bit-identical to the solo engine — pinned
across forced preemption/offload in ``tests/test_serve_multidevice.py``
— and the auditor's partition gate now runs against an *empty*
baseline at every mesh size.

Interpret mode
--------------
:func:`pallas_interpret` is the one place that decides it: a kernel
interprets exactly when JAX's default backend is not a TPU.  On a TPU
every kernel compiles through Mosaic, and a kernel the TPU lowering
refuses raises the compiler's error instead of quietly interpreting.
"""
from __future__ import annotations

import jax

__all__ = ["pallas_interpret"]


def pallas_interpret() -> bool:
    """Interpret mode for a Pallas call: exactly when the default
    backend is not a TPU."""
    return jax.default_backend() != "tpu"
