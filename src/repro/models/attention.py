"""Attention: GQA/MQA/MHA, causal + sliding-window, softcap, QKV bias.

``attend_full`` is the reference path used for training/prefill and for
the dry-run (on a real TPU the Pallas flash kernel in
``repro.kernels.flash_attention`` substitutes via ``use_kernel=True``;
both are validated against each other in the kernel test sweep).
``decode_attend`` consumes a KV cache for single-token decoding.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.dist.axisenv import (constrain, current_env, model_shard,
                                reduce_model)
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rope, softcap

__all__ = ["attn_init", "attn_apply", "attn_prefill", "attn_decode",
           "KVCache", "init_kv_cache",
           "PagedKVCache", "init_paged_kv_cache",
           "ZERO_PAGE", "DUMP_PAGE", "RESERVED_PAGES"]


def attn_init(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, k = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, k * hd), dtype),
        "wv": dense_init(ks[2], (d, k * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p |= {"bq": jnp.zeros((h * hd,), dtype),
              "bk": jnp.zeros((k * hd,), dtype),
              "bv": jnp.zeros((k * hd,), dtype)}
    return p


def _project_qkv(params, cfg: ModelConfig, x):
    """q [b, s, heads, hd], k and v [b, s, kv_heads, hd].  The head
    counts are read off the weights, which under a manual model axis
    hold this device's heads only."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, s, -1, hd)
    k = k.reshape(b, s, -1, hd)
    v = v.reshape(b, s, -1, hd)
    return q, k, v


def _out_proj(params, out):
    """Output projection.  Under a manual model axis each device holds
    its heads' rows of ``wo``: the partial products stay in float32
    through the sum over the axis and are rounded once, as one device's
    product is."""
    if model_shard() is None:
        return out @ params["wo"]
    return reduce_model(jnp.dot(out, params["wo"],
                                preferred_element_type=jnp.float32)
                        ).astype(out.dtype)


def _mask(s_q: int, s_kv: int, offset, local_window: Optional[int]):
    """Causal (+ optional sliding window) mask. offset = kv_len - q_len."""
    qi = jnp.arange(s_q)[:, None] + offset
    kj = jnp.arange(s_kv)[None, :]
    m = kj <= qi
    if local_window is not None:
        m &= kj > qi - local_window
    return m


def _sdpa(q, k, v, mask, cfg: ModelConfig):
    """q: [b,sq,h,hd]; k,v: [b,skv,kvh,hd] — grouped-query attention.

    K/V are expanded to the full query-head count so the whole
    computation shards cleanly on the head axis ("M"); the explicit
    constraints prevent GSPMD from replicating the O(s^2) score tensor
    across the GQA head reshape (which it otherwise does — see the
    §Perf log entry on the first smollm dry-run).  The Pallas flash
    kernel performs the same computation without materializing the
    expanded K/V on real TPUs.
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    # Heads shard on the model axis even when h < axis (GSPMD pads; the
    # idle-device cost shows up in the roofline and is a per-arch §Perf
    # note).  Leaving attention unconstrained lets GSPMD replicate the
    # O(s^2) score tensors — measured 3x worse peak memory on smollm.
    q = constrain(q, "B", None, "M", None)
    k = constrain(k, "B", None, "M", None)
    v = constrain(v, "B", None, "M", None)
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    logits = constrain(logits, "B", "M", None, None)
    logits = softcap(logits, cfg.attn_softcap)
    logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", w.astype(v.dtype), v)
    return out.reshape(b, sq, h * hd)


# Query-block size for the chunked (flash-style) path; sequences at or
# below 2*QBLOCK use the direct path.
QBLOCK = 1024


def _attend_causal(q, k, v, cfg: ModelConfig, window: Optional[int]):
    """Blocked causal (+ optional sliding-window) attention core.

    Long sequences use a *blocked* computation: query blocks are
    processed against only their causally (and window-) reachable key
    range with static slice bounds, so the materialized score tensor is
    O(s * QBLOCK) instead of O(s^2) and no FLOPs are spent on fully
    masked blocks — the pure-JAX mirror of the Pallas flash kernel's
    tiling (which substitutes on real TPUs).
    """
    s = q.shape[1]
    if s <= 2 * QBLOCK or s % QBLOCK:
        mask = _mask(s, s, 0, window)
        return _sdpa(q, k, v, mask, cfg)
    outs = []
    for qb in range(s // QBLOCK):
        qs, qe = qb * QBLOCK, (qb + 1) * QBLOCK
        if window is not None:
            ks = max(0, ((qs - window) // QBLOCK) * QBLOCK)
        else:
            ks = 0
        kslice = k[:, ks:qe]
        vslice = v[:, ks:qe]
        mask = _mask(QBLOCK, qe - ks, qs - ks, window)
        outs.append(_sdpa(q[:, qs:qe], kslice, vslice, mask, cfg))
    return jnp.concatenate(outs, axis=1)


def attn_apply(params, cfg: ModelConfig, x, positions, kind: str):
    """Full-sequence attention (train / prefill)."""
    q, k, v = _project_qkv(params, cfg, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    window = cfg.window_size if kind == "local" else None
    out = _attend_causal(q, k, v, cfg, window)
    return _out_proj(params, out)


def attn_prefill(params, cfg: ModelConfig, x, positions, kind: str,
                 cache_len: int, lengths=None):
    """Full-sequence attention that also materializes the decode cache.

    One forward over the whole prompt (same blocked core as
    ``attn_apply``) whose post-RoPE K/V land in a fresh ring/append
    cache of ``cache_len`` slots, ready for ``attn_decode`` to continue
    from position ``s``.  Prompts longer than the cache keep only the
    last ``cache_len`` positions (the only ones a ring buffer would
    retain), at their ring slots.

    ``lengths`` ([b] int32): per-sequence real prompt lengths for
    right-padded (length-bucketed) prefill.  The causal mask already
    keeps padded keys out of every valid query row, so the attention
    output below ``length`` is bit-identical to the unpadded forward;
    the cache scatter additionally drops rows at positions >=
    ``length`` (and below the ring horizon), leaving them zero exactly
    as ``init_kv_cache`` would.
    """
    q, k, v = _project_qkv(params, cfg, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    window = cfg.window_size if kind == "local" else None
    out = _attend_causal(q, k, v, cfg, window)

    s = x.shape[1]
    shape = (x.shape[0], cache_len) + k.shape[2:]
    if lengths is None:
        keep = min(s, cache_len)
        slots = jnp.arange(s - keep, s) % cache_len
        ck = jnp.zeros(shape, k.dtype).at[:, slots].set(k[:, -keep:])
        cv = jnp.zeros(shape, v.dtype).at[:, slots].set(v[:, -keep:])
        cache = KVCache(ck, cv, jnp.asarray(keep, jnp.int32))
    else:
        lengths = jnp.asarray(lengths, jnp.int32)
        p = jnp.arange(s)[None, :]
        live = (p < lengths[:, None]) & (p >= lengths[:, None] - cache_len)
        # dead rows scatter into a dump slot past the cache and are
        # sliced off; live slots are unique, so `set` is deterministic.
        slots = jnp.where(live, p % cache_len, cache_len)

        def scatter(rows, slots_b):
            buf = jnp.zeros((cache_len + 1,) + rows.shape[1:], rows.dtype)
            return buf.at[slots_b].set(rows)[:cache_len]

        ck = jax.vmap(scatter)(k, slots)
        cv = jax.vmap(scatter)(v, slots)
        keep = jnp.minimum(jnp.max(lengths), cache_len).astype(jnp.int32)
        cache = KVCache(ck, cv, keep)
    return _out_proj(params, out), cache


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------
class KVCache(NamedTuple):
    k: jnp.ndarray        # [b, cache_len, kv_heads, head_dim]
    v: jnp.ndarray
    length: jnp.ndarray   # [] int32 — tokens currently valid


def init_kv_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> KVCache:
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (batch, cache_len, kvh, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


# Reserved pool pages of every paged cache (KV and recurrent-state):
#   page 0 — ZERO: never written; block entries of a live slot's not-yet-
#            allocated logical pages point here, so gathers read exact
#            zeros (bit-identical to a fresh contiguous cache row).
#   page 1 — DUMP: write sink; block entries of *dead* (unoccupied) batch
#            slots point here so their decode writes land harmlessly
#            outside every live slot's pages.  Its content is garbage and
#            is only ever read by dead rows, whose outputs are ignored.
#
# On a data-parallel mesh the pool is built as ``shards`` equal extents,
# one per data shard, and EVERY shard carries its own ZERO/DUMP pair at
# the front of its extent (global ids ``g*ext + ZERO_PAGE`` /
# ``g*ext + DUMP_PAGE``): a device-local decode step must never reach a
# reserved page on another device.  ``shards == 1`` is exactly the old
# single-pool layout.
ZERO_PAGE = 0
DUMP_PAGE = 1
RESERVED_PAGES = 2


def shard_of_slot(batch: int, shards: int):
    """Data-axis shard owning each batch slot: slots are pinned in
    contiguous blocks (``slot // (batch/shards)``), matching how a
    ``P(data)`` layout splits the slot dim.  Returns [batch] int32."""
    if shards < 1 or batch % shards:
        raise ValueError(
            f"paged cache: batch {batch} must be a positive multiple of "
            f"shards {shards} (slots are pinned to data shards)")
    return jnp.arange(batch, dtype=jnp.int32) // (batch // shards)


def _shard_dump_ids(batch: int, n_pages: int, shards: int):
    """Per-slot DUMP page id ([batch] int32): the DUMP page of the
    shard-local pool extent the slot is pinned to."""
    if n_pages % shards:
        raise ValueError(
            f"paged cache: pool extent {n_pages} must divide into "
            f"shards {shards} equal per-device extents")
    ext = n_pages // shards
    return shard_of_slot(batch, shards) * ext + DUMP_PAGE


@dataclasses.dataclass(frozen=True)
class PagedKVCache:
    """Block-table paged decode cache of one attention layer.

    The logical cache a slot sees is identical to :class:`KVCache`'s
    ``[cache_len]`` ring/append buffer; physically the rows live in
    fixed-size pages of a shared pool, indirected per batch slot through
    ``block``.  Pages are allocated on first write and freed on retire
    by the serving-side :class:`repro.serve.paging.PageTable`; the model
    layer only reads/writes through the indirection.  ``page_size``,
    ``cache_len`` and ``kv_heads`` are static (pytree aux data), so one
    lowered decode step serves any block-table state.

    A pool row holds every KV head side by side, ``kv_heads*head_dim``
    wide: that minor axis fills the TPU's 128 lanes where ``head_dim``
    alone may not, so the chip lays the pool out page-major and a page
    is one contiguous block (see :mod:`repro.kernels.paged_attention`).
    Stacked over layer groups the pools are ``[G, n_pages, page_size,
    kv_heads*head_dim]``, and the decode step updates them in place.
    """

    kp: jnp.ndarray       # [n_pages, page_size, kv_heads*head_dim] pool
    vp: jnp.ndarray
    block: jnp.ndarray    # [b, n_logical_pages] int32 pool page ids
    length: jnp.ndarray   # [] int32 — high-water mark (as KVCache)
    page_size: int = dataclasses.field(metadata=dict(static=True))
    cache_len: int = dataclasses.field(metadata=dict(static=True))
    kv_heads: int = dataclasses.field(metadata=dict(static=True))


jax.tree_util.register_dataclass(
    PagedKVCache, data_fields=("kp", "vp", "block", "length"),
    meta_fields=("page_size", "cache_len", "kv_heads"))


def n_logical_pages(cache_len: int, page_size: int) -> int:
    """Pages covering a ``cache_len``-slot logical cache (last may be
    partial: the gathered view is sliced back to ``cache_len``)."""
    return -(-cache_len // page_size)


def init_paged_kv_cache(cfg: ModelConfig, batch: int, cache_len: int,
                        page_size: int, n_pages: int, dtype,
                        shards: int = 1) -> PagedKVCache:
    """Fresh pool of ``n_pages`` (incl. the reserved pages of each of the
    ``shards`` per-device extents) + all-DUMP block tables: every slot is
    dead until the page table assigns pages.  Dead slots dump into the
    DUMP page of *their own shard's* extent so a device-local decode
    never writes across the data axis (``shards == 1``: plain DUMP)."""
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    n_lp = n_logical_pages(cache_len, page_size)
    shape = (n_pages, page_size, kvh * hd)
    dump = _shard_dump_ids(batch, n_pages, shards)
    return PagedKVCache(
        kp=jnp.zeros(shape, dtype), vp=jnp.zeros(shape, dtype),
        block=jnp.broadcast_to(dump[:, None], (batch, n_lp)),
        length=jnp.zeros((), jnp.int32),
        page_size=page_size, cache_len=cache_len, kv_heads=kvh)


def paged_kv_view(cache: PagedKVCache, layer=None):
    """Gather the block-table indirection into the contiguous
    ``[b, cache_len, kv_heads, head_dim]`` layout :class:`KVCache`
    stores directly.  Values land in the exact same slot order, which is
    what makes paged attention bit-identical to contiguous attention.
    ``layer`` picks one layer of pools stacked over layer groups (one
    gather from the stacked pool, which is never sliced whole)."""
    b, n_lp = cache.block.shape
    at = () if layer is None else (layer,)
    shape = (b, n_lp * cache.page_size, cache.kv_heads, -1)
    k = cache.kp[(*at, cache.block)].reshape(shape)
    v = cache.vp[(*at, cache.block)].reshape(shape)
    return k[:, :cache.cache_len], v[:, :cache.cache_len]


def attn_decode(params, cfg: ModelConfig, x, cache, pos, kind: str,
                backend: str = "gather", layer=None):
    """One-token decode. x: [b, 1, d]; pos: [] or [b] int32 absolute
    position (vector = per-slot positions for continuous batching).

    ``local`` layers use the cache as a ring buffer of ``window_size``
    slots; ``global`` layers append at ``pos``.  ``cache`` is either a
    contiguous :class:`KVCache` or a block-table :class:`PagedKVCache`;
    the attention math runs on the same ``[b, cache_len]`` slot layout
    either way (paged caches gather their pages into it), so the two
    forms decode bit-identically.

    ``backend`` (paged caches only): ``"gather"`` materializes the
    contiguous logical view each step (bit-identical to the contiguous
    cache); ``"pallas_paged"`` runs the Pallas decode kernel
    (:mod:`repro.kernels.paged_attention`) that reads K/V pages through
    the block-table indirection in place — no gathered view is ever
    materialized.  The kernel mirrors the gather math up to
    accumulation order (online softmax over pages), so generations are
    identical while logits agree to interpret-mode tolerance.

    ``layer`` (paged caches only): the cache's pools are stacked over
    layer groups (``[G, n_pages, page_size, kv_heads*head_dim]``) and
    this layer is group ``layer`` of them.  The new row is written into
    the stacked pool and the attention reads it there, so a decode step
    that carries the stacked pools through its layer loop updates them
    in place; the returned cache holds the whole stacked pools.
    """
    if backend not in ("gather", "pallas_paged"):
        raise ValueError(f"unknown decode backend {backend!r}")
    q, k_new, v_new = _project_qkv(params, cfg, x)
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    posv = pos[:, None] if per_slot else jnp.full((b, 1), pos, jnp.int32)
    q = rope(q, posv, cfg.rope_theta)
    k_new = rope(k_new, posv, cfg.rope_theta)

    paged = isinstance(cache, PagedKVCache)
    if backend == "pallas_paged" and not paged:
        raise ValueError(
            "decode backend 'pallas_paged' consumes block tables; it "
            "requires a PagedKVCache (serve with paged=PagedCacheConfig)")
    cache_len = cache.cache_len if paged else cache.k.shape[1]
    # cache_len == window_size for local layers (ring buffer), == max_len
    # for global layers (plain append, since pos < max_len).
    slot = pos % cache_len
    if paged:
        # write the new row through the block table (a one-row scatter
        # into the pool page holding ``slot`` — PageTable.prepare_step
        # assigned it; dead slots' tables point at DUMP).
        jdx, off = slot // cache.page_size, slot % cache.page_size
        if per_slot:
            pid = cache.block[jnp.arange(b), jdx]
        else:
            pid = cache.block[:, jdx]
        at = () if layer is None else (layer,)
        kp = cache.kp.at[(*at, pid, off)].set(k_new[:, 0].reshape(b, -1))
        vp = cache.vp.at[(*at, pid, off)].set(v_new[:, 0].reshape(b, -1))
        new_cache = dataclasses.replace(cache, kp=kp, vp=vp)
        if backend == "pallas_paged":
            # the kernel walks the block table in place; no logical view
            from repro.kernels.paged_attention.ops import paged_attention
            kvh, hd = k_new.shape[2], cfg.resolved_head_dim
            g = q.shape[2] // kvh
            posb = pos if per_slot else jnp.full((b,), pos, jnp.int32)
            out = paged_attention(
                q[:, 0].reshape(b, kvh, g, hd), kp, vp, new_cache.block,
                posb, layer, cache_len=cache_len,
                window=(cfg.window_size if kind == "local" else None),
                softcap=cfg.attn_softcap)
            out = out.reshape(b, 1, -1)
            new_len = jnp.minimum(jnp.max(pos) + 1, cache_len)
            new_cache = dataclasses.replace(
                new_cache, length=new_len.astype(jnp.int32))
            return _out_proj(params, out), new_cache
        k, v = paged_kv_view(new_cache, layer)
    elif per_slot:
        rows = jnp.arange(b)
        k = cache.k.at[rows, slot].set(k_new[:, 0])
        v = cache.v.at[rows, slot].set(v_new[:, 0])
    else:
        k = jax.lax.dynamic_update_slice(cache.k, k_new, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache.v, v_new, (0, slot, 0, 0))

    kv_pos = _cache_positions(cache_len, pos)   # [L] or [b, L]
    valid = kv_pos >= 0
    if kind == "local" and cfg.window_size is not None:
        valid &= kv_pos > (pos[:, None] if per_slot else pos) - cfg.window_size
    if valid.ndim == 1:
        valid = valid[None]                      # [1, L] broadcasts over b
    kvh, hd = k_new.shape[2], cfg.resolved_head_dim
    scale = hd ** -0.5
    g = q.shape[2] // kvh
    # Cache sharding choice (mirrors serve.engine.cache_specs): enough
    # KV heads to fill the model axis -> shard heads; otherwise shard
    # the cache *length* (flash-decode-style distributed attention with
    # a GSPMD all-reduce over the softmax stats).  The grouped einsum
    # keeps the cache unexpanded: decode is cache-bandwidth-bound and
    # repeating K/V g-fold would inflate the memory roofline term.
    env = current_env()
    msize = env.size("M") if env else None
    if env is not None and env.seq is not None:
        kv_tags = ("B", "S", None, None)       # long-context: shard length
    elif msize and kvh % msize == 0:
        kv_tags = ("B", None, "M", None)       # enough heads: shard heads
    else:
        kv_tags = ("B", "M", None, None)       # few heads: shard length on M
    k = constrain(k, *kv_tags)
    v = constrain(v, *kv_tags)
    qh = q.reshape(b, 1, kvh, g, hd)
    # RoPE for cached keys was applied at insert time; kv cache stores
    # post-rope keys, so attend directly.
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qh, k).astype(jnp.float32) * scale
    logits = softcap(logits, cfg.attn_softcap)
    logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v).reshape(b, 1, -1)
    new_len = jnp.minimum(jnp.max(pos) + 1, cache_len).astype(jnp.int32)
    if paged:
        new_cache = dataclasses.replace(new_cache, length=new_len)
    else:
        new_cache = KVCache(k, v, new_len)
    return _out_proj(params, out), new_cache


def _cache_positions(cache_len: int, pos):
    """Absolute position stored in each ring slot (-1 if empty).

    Slot s holds the newest absolute position p <= pos with p % L == s.
    ``pos`` may be scalar (-> [L]) or [b] (-> [b, L]).
    """
    slots = jnp.arange(cache_len)
    cur_slot = pos % cache_len
    newest = pos[..., None] - ((cur_slot[..., None] - slots) % cache_len)
    return jnp.where(newest >= 0, newest, -1)
