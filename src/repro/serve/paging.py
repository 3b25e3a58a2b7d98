"""Block-table page management for the serving cache.

The model layer defines *what* a paged cache is
(:class:`repro.models.attention.PagedKVCache` and the recurrent-state
mirrors); this module owns the page *lifecycle* the paper's energy
model cares about: which pages are resident, which logical rows they
hold, and every byte that crosses the accelerator boundary when they
move.

One :class:`PageTable` manages every cache stream of a model — one KV
stream per attention pattern position (``groups``/``tail``), one
state-page stream per recurrent (ssm/rglru) position — so all 10
architectures serve through the same allocator:

* **allocate-on-write** — admission takes exactly the pages the
  prompt's rows need (``ceil(min(plen, cache_len)/page_size)`` per KV
  stream, one state page per recurrent stream); decode allocates a
  fresh zeroed page only when a slot's write position crosses into an
  unassigned logical page, so a slot's footprint tracks its actual
  context, not ``max_ctx``.
* **free-on-retire** — a retired slot's pages return to the free list
  and its block-table rows point back at the DUMP page.
* **offload / restore** — a preempted slot's resident pages are copied
  to host memory (:func:`jax.device_get` into numpy), freed on
  device, and later restored bit-identically into freshly allocated
  pages (the block table re-targets; content is unchanged).  The
  engine accounts both directions as page-in/page-out traffic
  (:mod:`repro.serve.telemetry`).
* **prefix sharing + copy-on-write** (PR 10) — identical prompt
  prefixes hash to the same physical pages.  A KV page's content is a
  pure function of the token prefix up to and including its tokens
  (attention is causal), so one chained content hash per page-granular
  token chunk (:func:`prefix_page_keys`) keys a per-(stream, shard)
  registry of live pages.  Admission attaches registry hits instead of
  allocating: the block-table row points at the shared page, the
  admission scatter for that row is redirected to the shard's DUMP
  page, and the page's refcount rises.  Decode forks a private copy on
  the first write into a shared page (refcount > 1: device-side page
  copy + block re-target; refcount == 1: the sole owner unregisters it
  in place and writes through) — so ring wraps and appends into a
  shared partial tail page stay bit-identical to unshared serving.
  Refcount lifecycle: register-on-admit (refcount 1), +1 per attach,
  -1 on fork/release/offload, unregister + free at zero.  A registered
  page therefore lives exactly as long as one admitted slot still
  references it — sharing is an in-flight property, which is why the
  engine's prefix-aware scheduler batches same-prefix requests.
  Registries are strictly per shard: a slot only ever attaches pages
  inside its own device-local extent, preserving the PR 8 no-pool-
  collective layout.  State (ssm/rglru) pages are rewritten every
  decode step and never shared; the engine's full-prompt memo restores
  them from a host snapshot instead.

Per-stream pool capacity is ``resident_pages`` + the reserved pages
(ZERO, DUMP — :mod:`repro.models.attention`).  ``resident_pages`` must
cover one fully decoded slot (``max(n_logical_pages)`` over streams):
with that floor, preempting down to a single live slot always frees
enough pages, so the engine can guarantee forward progress under any
budget it accepts.

**Device-local layout (``shards > 1``).**  On a data-parallel mesh the
allocator splits every pool into ``shards`` equal extents — one per
data shard, each fronted by its own ZERO/DUMP pair — and pins batch
slot ``s`` to extent ``s // (max_batch/shards)``, exactly the rows a
``P(data)`` slot layout places on that device.  Allocation then runs a
*per-(stream, shard)* free list: a slot only ever receives pages from
its own extent, so the ``shard_map`` decode step
(:func:`repro.serve.engine.build_decode_step`) reads and writes pool
pages strictly device-locally and no collective with a pool operand is
lowered at any mesh size (the drained ``pool-collective`` baseline
family of ``repro.analysis``).  All budget floors become per-shard:
every shard must hold one fully decoded slot.  ``shards == 1`` is the
original single-pool allocator, bit for bit.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import (DUMP_PAGE, RESERVED_PAGES, ZERO_PAGE,
                                    KVCache, PagedKVCache, n_logical_pages,
                                    paged_kv_view)
from repro.models.rglru import PagedRGLRUCache, RGLRUCache
from repro.models.ssm import PagedSSMCache, SSMCache
from repro.models.transformer import TransformerLM
from repro.serve import spans

__all__ = ["PagedCacheConfig", "PageTable", "PagePayload", "PageTableError",
           "PrefixSharingConfig", "PrefixKeys", "prefix_page_keys",
           "logical_view", "slot_floor"]


class PageTableError(RuntimeError):
    """Allocator-invariant violation inside :class:`PageTable` — raised
    with the slot, stream, and live-slot set named so an engine bug
    surfaces as a diagnosable serving error, not a bare ``KeyError``."""


def slot_floor(cfg, max_ctx: int, page_size: int) -> int:
    """Pages one fully decoded slot needs in its largest KV stream —
    THE budget floor: ``resident_pages`` below this can deadlock with
    every other slot already offloaded.  Single source of the rule for
    both the eager :meth:`PagedCacheConfig.validate` and
    :class:`PageTable`'s own defense."""
    floor = 1
    for kind in cfg.all_kinds:
        if kind in ("global", "local"):
            L = cfg.decode_cache_len(kind, max_ctx)
            floor = max(floor, n_logical_pages(L, page_size))
    return floor


# ---------------------------------------------------------------------------
# Prefix-sharing keys
# ---------------------------------------------------------------------------
_CHAIN_SEED = b"rtc-prefix-v1"


@dataclasses.dataclass(frozen=True)
class PrefixKeys:
    """Content-addressed page keys of one prompt.

    ``full[j]`` is the chained digest of token pages ``0..j`` — equal
    across two prompts iff their first ``(j+1)*page_size`` tokens are
    equal, so it keys the j-th full KV page in every stream.  ``tail``
    keys the partial last page (chain- and length-sensitive; ``None``
    when the prompt is page-aligned).  ``whole`` digests the entire
    prompt (the engine's full-prompt memo key) and ``group`` is the
    scheduler's batching key (first full page, or ``whole`` for
    prompts shorter than one page).
    """

    full: Tuple[bytes, ...]
    tail: Optional[bytes]
    whole: bytes
    group: bytes


def prefix_page_keys(tokens, page_size: int) -> PrefixKeys:
    """Chain-hash a prompt into per-page content keys.

    ``key_j = H(key_{j-1} || tokens[j*P:(j+1)*P])`` over full pages —
    the vLLM-style chaining that makes a page key identify the whole
    token prefix behind it, not just the page's own tokens (a KV page's
    content depends on every earlier token through causal attention).
    One hash chain serves all cache streams: per-stream registries map
    the same key to their own physical page.
    """
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    toks = np.asarray(tokens, np.int32).reshape(-1)
    chain = hashlib.sha1(_CHAIN_SEED).digest()
    full: List[bytes] = []
    n_full = toks.size // page_size
    for j in range(n_full):
        chain = hashlib.sha1(
            chain + toks[j * page_size:(j + 1) * page_size].tobytes()
        ).digest()
        full.append(chain)
    rem = toks.size - n_full * page_size
    tail = (hashlib.sha1(chain + b"tail"
                         + toks[n_full * page_size:].tobytes()).digest()
            if rem else None)
    whole = tail if tail is not None else (full[-1] if full else chain)
    group = full[0] if full else whole
    return PrefixKeys(full=tuple(full), tail=tail, whole=whole, group=group)


@dataclasses.dataclass(frozen=True)
class PrefixSharingConfig:
    """Prefix-sharing knobs (``PagedCacheConfig.sharing``).

    ``enabled``      — master switch; ``None``/disabled serves exactly
                       the pre-sharing allocator, bit for bit.
    ``schedule``     — pending-queue admission order: ``"prefix"``
                       groups same-prefix requests (group order = first
                       arrival, so FCFS progress is preserved) to
                       maximize in-flight hits; ``"fifo"`` keeps raw
                       arrival order.  Generations are bit-independent
                       of the schedule (sampling keys are (request,
                       token-index)-addressed), only the hit rate moves.
    ``memo_size``    — full-prompt memo entries kept per serve call
                       (prefill logits + recurrent-state snapshot,
                       host-resident; FIFO eviction).
    """

    enabled: bool = True
    schedule: str = "prefix"
    memo_size: int = 64

    def __post_init__(self):
        if self.schedule not in ("prefix", "fifo"):
            raise ValueError(
                f"PrefixSharingConfig.schedule must be 'prefix' or 'fifo', "
                f"got {self.schedule!r}")
        if self.memo_size < 0:
            raise ValueError(
                f"PrefixSharingConfig.memo_size must be >= 0, "
                f"got {self.memo_size}")


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    """Engine-facing knobs of the paged cache.

    ``page_size``       — tokens per KV page (the paper's mapping-policy
                          granularity: one page == one unit of DRAM-row
                          placement and of offload traffic).
    ``resident_pages``  — device-resident page budget per KV stream
                          (excl. the 2 reserved pages).  When live slots
                          need more, the engine preempts a victim and
                          offloads its pages to host.
    ``max_ctx``         — logical context capacity per slot; ``None``
                          means the engine's ``max_len``.  May exceed
                          ``max_len``: decode keeps appending pages past
                          the prefill cap, which is how requests outgrow
                          the old contiguous per-slot allocation.
    ``state_pages``     — pool extent per recurrent *state* stream,
                          including the reserved pages (``None`` =
                          ``max_batch + shards * RESERVED_PAGES``, the
                          minimum that can hold every slot).  State
                          pools shard their page dim across the data
                          axes exactly like KV pools, but only when the
                          extent divides the axis — on a mesh, size
                          this like ``resident_pages`` (a per-device
                          share times the device count) or the pool
                          replicates and the per-device state bill
                          grows with the mesh.
    ``shards``          — device-local pool extents to build
                          (:mod:`repro.serve.paging` layout note).
                          The default 1 lets the engine auto-resolve
                          from its mesh's data extent
                          (:meth:`repro.dist.sharding.ShardingPolicy.decode_shards`);
                          set it explicitly to build a mesh-shaped
                          cache geometry on a different (e.g. solo
                          compile-only) mesh, as the partitioning
                          auditor does.
    ``sharing``         — prefix-sharing/copy-on-write knobs
                          (:class:`PrefixSharingConfig`); ``None``
                          (default) disables sharing entirely and
                          serves exactly the pre-sharing allocator.

    Field-local constraints are checked at construction; the
    cross-field budget floor (``resident_pages`` must hold one fully
    decoded slot, which needs the model's layer mix) is checked by
    :meth:`validate`, which the engine calls before lowering anything —
    a bad config fails eagerly with the offending field named instead
    of deep inside :class:`PageTable`.
    """

    page_size: int = 16
    resident_pages: Optional[int] = None
    max_ctx: Optional[int] = None
    state_pages: Optional[int] = None
    shards: int = 1
    sharing: Optional[PrefixSharingConfig] = None

    def __post_init__(self):
        if self.shards < 1:
            raise ValueError(
                f"PagedCacheConfig.shards must be >= 1 (device-local pool "
                f"extents), got {self.shards}")
        if self.resident_pages is not None and self.resident_pages % self.shards:
            raise ValueError(
                f"PagedCacheConfig.resident_pages={self.resident_pages} must "
                f"split evenly across shards={self.shards} device-local "
                f"extents")
        if self.state_pages is not None and self.state_pages % self.shards:
            raise ValueError(
                f"PagedCacheConfig.state_pages={self.state_pages} must split "
                f"evenly across shards={self.shards} device-local extents")
        if self.page_size < 1:
            raise ValueError(
                f"PagedCacheConfig.page_size must be > 0 (tokens per KV "
                f"page), got {self.page_size}")
        if self.resident_pages is not None and self.resident_pages < 1:
            raise ValueError(
                f"PagedCacheConfig.resident_pages must be >= 1 when set "
                f"(device page budget per KV stream), got "
                f"{self.resident_pages}")
        if self.state_pages is not None and self.state_pages < 1:
            raise ValueError(
                f"PagedCacheConfig.state_pages must be >= 1 when set "
                f"(state-stream pool extent incl. reserved pages), got "
                f"{self.state_pages}")
        if self.max_ctx is not None and self.max_ctx < 1:
            raise ValueError(
                f"PagedCacheConfig.max_ctx must be >= 1 when set "
                f"(logical context capacity per slot), got {self.max_ctx}")

    def slot_floor(self, cfg, max_ctx: int) -> int:
        """Pages one fully decoded slot needs in its largest KV stream
        (the guaranteed-progress floor for ``resident_pages``)."""
        return slot_floor(cfg, max_ctx, self.page_size)

    def validate(self, cfg, max_ctx: Optional[int] = None) -> None:
        """Cross-field checks against a model config (and the engine's
        resolved ``max_ctx``, defaulting to this config's own)."""
        ctx = int(max_ctx if max_ctx is not None else (self.max_ctx or 0))
        if ctx < 1:
            raise ValueError(
                "PagedCacheConfig.validate needs a positive max_ctx "
                "(none set on the config and none passed)")
        floor = self.slot_floor(cfg, ctx)
        if (self.resident_pages is not None
                and self.resident_pages // self.shards < floor):
            per = (f" per shard ({self.shards} device-local extents)"
                   if self.shards > 1 else "")
            raise ValueError(
                f"PagedCacheConfig.resident_pages={self.resident_pages} "
                f"cannot hold one fully decoded slot{per}: max_ctx={ctx} at "
                f"page_size={self.page_size} needs {floor} pages in the "
                f"largest KV stream; the engine could deadlock with every "
                f"other slot already offloaded")


class _Stream:
    """Host-side allocator state of one cache stream.

    ``free`` is one free list *per data shard*: ``free[g]`` holds only
    global page ids inside shard ``g``'s pool extent
    ``[g*ext, (g+1)*ext)``, whose first ``RESERVED_PAGES`` ids are that
    shard's private ZERO/DUMP pair (:meth:`zero` / :meth:`dump`).

    Prefix-sharing registry (KV streams only): ``shared[g]`` maps a
    content key (:func:`prefix_page_keys`) to the live page holding
    that content inside shard ``g``'s extent; ``ref[pid]`` counts the
    slots whose block table points at a registered page, and
    ``rkey[pid]`` remembers the (shard, key) entry so forks and
    releases can unregister without a reverse scan."""

    __slots__ = ("where", "kind", "cache_len", "n_lp", "n_pages", "shards",
                 "ext", "free", "slot_pages", "shared", "ref", "rkey")

    def __init__(self, where, kind, cache_len, n_lp, n_pages, shards=1):
        self.where = where            # ("groups", i) | ("tail", i)
        self.kind = kind
        self.cache_len = cache_len    # None for state streams
        self.n_lp = n_lp              # logical pages (1 for state streams)
        self.n_pages = n_pages        # pool extent incl. reserved pages
        self.shards = shards
        assert n_pages % shards == 0, (where, n_pages, shards)
        self.ext = n_pages // shards  # per-shard pool extent
        self.free: List[List[int]] = []
        self.reset_free()
        # KV: {slot: {jdx: pid}}; state: {slot: pid}
        self.slot_pages: Dict[int, object] = {}
        self.shared: List[Dict[bytes, int]] = [{} for _ in range(shards)]
        self.ref: Dict[int, int] = {}
        self.rkey: Dict[int, Tuple[int, bytes]] = {}

    def reset_free(self) -> None:
        self.free = [list(range(g * self.ext + RESERVED_PAGES,
                                (g + 1) * self.ext))
                     for g in range(self.shards)]

    def reset_sharing(self) -> None:
        self.shared = [{} for _ in range(self.shards)]
        self.ref.clear()
        self.rkey.clear()

    def zero(self, g: int) -> int:
        """Global id of shard ``g``'s ZERO page."""
        return g * self.ext + ZERO_PAGE

    def dump(self, g: int) -> int:
        """Global id of shard ``g``'s DUMP page."""
        return g * self.ext + DUMP_PAGE

    @property
    def is_state(self) -> bool:
        return self.cache_len is None


@dataclasses.dataclass
class PagePayload:
    """Host-resident copy of one offloaded slot (all streams).

    ``kv[si] = (jdx->row, k_pages, v_pages)`` with contents shaped
    ``[G?, n_rows, page_size, kv_heads*head_dim]``;
    ``state[si] = (conv, h)``.  ``tokens`` is the slot's context length
    at offload time (for traffic accounting).
    """

    kv: Dict[int, Tuple[Dict[int, int], np.ndarray, np.ndarray]]
    state: Dict[int, Tuple[np.ndarray, np.ndarray]]
    tokens: int

    def pages_needed(self) -> Dict[int, int]:
        return {si: len(jdx_rows) for si, (jdx_rows, _, _) in self.kv.items()}


class PageTable:
    """Page allocator + jitted cache-update ops for one engine.

    All device-side mutation goes through jitted functions whose cache
    output can be pinned to the decode step's shardings
    (``cache_shardings``), so the admit/decode/offload round trip stays
    layout-stable on real meshes.
    """

    def __init__(self, model: TransformerLM, max_batch: int, max_ctx: int,
                 page_size: int, resident_pages: Optional[int] = None,
                 cache_shardings=None, state_pages: Optional[int] = None,
                 shards: int = 1):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self.model = model
        self.cfg = model.cfg
        self.max_batch = int(max_batch)
        self.max_ctx = int(max_ctx)
        self.page_size = int(page_size)
        self.shards = int(shards)
        if self.max_batch % self.shards:
            raise ValueError(
                f"max_batch={self.max_batch} slots cannot pin evenly to "
                f"shards={self.shards} device-local pool extents (slots "
                f"ride the data axes in contiguous blocks)")
        self.slots_per_shard = self.max_batch // self.shards
        self._csh = cache_shardings

        self.streams: List[_Stream] = []
        min_budget = slot_floor(self.cfg, self.max_ctx, self.page_size)
        if resident_pages is None:
            # ample default: every slot fully decoded stays resident
            resident_pages = min_budget * self.max_batch
        if resident_pages % self.shards:
            raise ValueError(
                f"resident_pages={resident_pages} must split evenly across "
                f"shards={self.shards} device-local extents")
        if resident_pages // self.shards < min_budget:
            per = (f" in each of the {self.shards} device-local extents"
                   if self.shards > 1 else "")
            raise ValueError(
                f"resident_pages={resident_pages} cannot hold one fully "
                f"decoded slot{per} ({min_budget} pages of {page_size} "
                f"tokens for max_ctx={self.max_ctx}); the engine could "
                f"deadlock with every other slot already offloaded")
        self.resident_pages = int(resident_pages)
        # every shard carries its own reserved ZERO/DUMP pair
        self.n_pages = self.resident_pages + self.shards * RESERVED_PAGES

        state_floor = self.max_batch + self.shards * RESERVED_PAGES
        if state_pages is None:
            state_pages = state_floor
        if state_pages % self.shards:
            raise ValueError(
                f"state_pages={state_pages} must split evenly across "
                f"shards={self.shards} device-local extents")
        if state_pages < state_floor:
            raise ValueError(
                f"state_pages={state_pages} cannot hold every slot's "
                f"recurrent state: max_batch={self.max_batch} slots need "
                f"{state_floor} pages (one each plus {RESERVED_PAGES} "
                f"reserved per shard x {self.shards} shard(s))")
        self.state_pages = int(state_pages)

        for where, kind in self._positions():
            if kind in ("global", "local"):
                L = self.cfg.decode_cache_len(kind, self.max_ctx)
                self.streams.append(_Stream(
                    where, kind, L, n_logical_pages(L, page_size),
                    self.n_pages, self.shards))
            else:
                self.streams.append(_Stream(
                    where, kind, None, 1, self.state_pages, self.shards))

        # per-serve prefix-sharing counters (reset() zeroes them); tests
        # pin the allocation-once bound through these
        self.stats: Dict[str, int] = {
            "pages_registered": 0, "pages_attached": 0,
            "cow_forks": 0, "full_attaches": 0}
        # per-stream layer-token accounting of the most recent admit /
        # admit_cached — the scheduler turns this into the telemetry
        # prefix-hit traffic class
        self.last_admit: Optional[Dict[str, int]] = None

        self.bind_shardings(cache_shardings)

    def shard_of(self, slot: int) -> int:
        """Data shard (pool extent) batch slot ``slot`` is pinned to."""
        return int(slot) // self.slots_per_shard

    def bind_shardings(self, cache_shardings=None) -> None:
        """(Re)build the jitted cache ops, pinning their cache output to
        ``cache_shardings`` (the decode step's) so the admit/decode/
        offload round trip is layout-stable on real meshes.  The engine
        calls this once the decode step — and therefore the cache
        placement — exists."""
        self._csh = cache_shardings
        # donate the cache arg (as the decode step does): these ops
        # rewrite a slice of the pools, and without donation each admit/
        # retire/page-assign would copy every pool buffer on device.
        # fetch must NOT donate — offload reads pages out of a cache
        # that stays live.
        kw = {"donate_argnums": (0,)}
        if cache_shardings is not None:
            kw["out_shardings"] = cache_shardings
        # stable program names (``jit_page_table_*``) in a trace
        named = spans.named
        self._insert_jit = jax.jit(named("page_table_insert",
                                         self._insert_fn), **kw)
        self._release_jit = jax.jit(named("page_table_release",
                                          self._release_fn), **kw)
        self._restore_jit = jax.jit(named("page_table_restore",
                                          self._restore_fn), **kw)
        self._attach_jit = jax.jit(named("page_table_attach",
                                         self._attach_fn), **kw)
        self._assign_jit = {
            si: jax.jit(named(f"page_table_assign_{si}",
                              functools.partial(self._assign_fn, si)), **kw)
            for si, st in enumerate(self.streams) if not st.is_state}
        self._fork_jit = {
            si: jax.jit(named(f"page_table_fork_{si}",
                              functools.partial(self._fork_fn, si)), **kw)
            for si, st in enumerate(self.streams) if not st.is_state}
        self._fetch_jit = {
            si: jax.jit(named(f"page_table_fetch_{si}", functools.partial(
                self._fetch_state_fn if st.is_state else self._fetch_kv_fn,
                si)))
            for si, st in enumerate(self.streams)}

    def reset(self) -> None:
        """Drop all allocations (fresh serve call: every page free,
        every sharing registry empty, stats zeroed)."""
        for st in self.streams:
            st.reset_free()
            st.slot_pages.clear()
            st.reset_sharing()
        for k in self.stats:
            self.stats[k] = 0
        self.last_admit = None

    # ------------------------------------------------------------- structure
    def _positions(self):
        for i, kind in enumerate(self.cfg.attn_pattern):
            yield ("groups", i), kind
        for i, kind in enumerate(self.cfg.pattern_tail):
            yield ("tail", i), kind

    def _get(self, cache, where):
        return cache[where[0]][where[1]]

    @staticmethod
    def _replace(cache, where, node):
        top, i = where
        seq = list(cache[top])
        seq[i] = node
        return {**cache, top: tuple(seq)}

    def init_cache(self):
        return self.model.init_paged_cache(
            self.max_batch, self.max_ctx, self.page_size, self.n_pages,
            state_pages=self.state_pages, shards=self.shards)

    # -------------------------------------------------------------- sizing
    def kv_pages_for(self, tokens: int, stream: _Stream) -> int:
        """Pages prefilling ``tokens`` prompt rows writes in a stream
        (a prompt past the ring length wraps and touches every page)."""
        return n_logical_pages(
            min(max(int(tokens), 1), stream.cache_len), self.page_size)

    # --------------------------------------------------- prefix sharing
    @staticmethod
    def _shareable(st: _Stream, plen: int) -> bool:
        """A stream's prefill pages are content-addressable only when
        the prompt fits its ring (``plen <= cache_len``): a wrapped
        prefill overwrites page rows, so page content stops being a
        pure function of the token prefix.  State streams never share
        (rewritten every decode step)."""
        return (not st.is_state) and plen <= st.cache_len

    def _stream_layers(self, st: _Stream) -> int:
        """Model layers stacked behind one page id of this stream —
        the layer-token multiplier for hit/fork traffic accounting."""
        return self.cfg.n_groups if st.where[0] == "groups" else 1

    def _page_key(self, keys: PrefixKeys, j: int, plen: int):
        """Content key of prompt page ``j`` (full-page chain digest, or
        the tail digest for the partial last page)."""
        return (keys.full[j] if (j + 1) * self.page_size <= plen
                else keys.tail)

    def _register(self, st: _Stream, g: int, key: bytes, pid: int) -> None:
        st.shared[g][key] = pid
        st.ref[pid] = 1
        st.rkey[pid] = (g, key)
        self.stats["pages_registered"] += 1

    def _unregister(self, st: _Stream, pid: int) -> None:
        g, key = st.rkey.pop(pid)
        del st.ref[pid]
        if st.shared[g].get(key) == pid:
            del st.shared[g][key]

    def _decref(self, st: _Stream, g: int, pid: int) -> None:
        """Drop one block-table reference to a registered page; the
        page frees (and leaves the registry) when nobody points at it."""
        st.ref[pid] -= 1
        if st.ref[pid] == 0:
            self._unregister(st, pid)
            st.free[g].append(pid)

    def fully_shareable(self, plen: int) -> bool:
        """Whether every KV stream can content-address a ``plen``-token
        prompt (no ring wrap anywhere) — the engine's condition for
        whole-prompt memoization: only then do the registered pages plus
        a state snapshot reconstruct the complete admission."""
        return all(self._shareable(st, plen) for st in self.streams
                   if not st.is_state)

    def _pages_missing(self, st: _Stream, g: int, plen: int,
                       keys: Optional[PrefixKeys]) -> int:
        """Fresh pages admitting a ``plen`` prompt would pop from shard
        ``g``'s free list in this stream (registry hits cost none)."""
        need = self.kv_pages_for(plen, st)
        if keys is None or not self._shareable(st, plen):
            return need
        return sum(1 for j in range(need)
                   if st.shared[g].get(self._page_key(keys, j, plen)) is None)

    def can_admit(self, plen: int, slot: int,
                  keys: Optional[PrefixKeys] = None) -> bool:
        """Whether ``slot``'s shard has pages for a ``plen``-token
        prompt in every stream (allocation is strictly shard-local).
        With ``keys``, registry hits are free — only the miss pages
        need free-list capacity."""
        g = self.shard_of(slot)
        for st in self.streams:
            need = 1 if st.is_state else self._pages_missing(st, g, plen, keys)
            if len(st.free[g]) < need:
                return False
        return True

    def can_admit_cached(self, slot: int, plen: int,
                         keys: Optional[PrefixKeys]) -> bool:
        """Whether the whole prompt is resident in ``slot``'s shard:
        every KV page of every stream is registered (full skip needs no
        prefill compute at all) and each state stream has a free page
        for the host-snapshot restore."""
        if keys is None:
            return False
        g = self.shard_of(slot)
        for st in self.streams:
            if st.is_state:
                if not st.free[g]:
                    return False
                continue
            if not self._shareable(st, plen):
                return False
            for j in range(self.kv_pages_for(plen, st)):
                if st.shared[g].get(self._page_key(keys, j, plen)) is None:
                    return False
        return True

    def free_page_counts(self) -> Dict[Tuple[str, int], int]:
        return {st.where: sum(len(f) for f in st.free)
                for st in self.streams}

    # --------------------------------------------------- placement geometry
    _ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}

    def stream_name(self, si: int) -> str:
        st = self.streams[si]
        return f"{'state' if st.is_state else 'kv'}:{st.where[0]}{st.where[1]}"

    def stream_names(self) -> Tuple[str, ...]:
        """Stable stream labels, in stream-list order — the binding
        contract between a :class:`repro.core.trace.PageAccessTrace`
        and the :class:`repro.core.placement.StreamGeometry` set."""
        return tuple(self.stream_name(si) for si in range(len(self.streams)))

    def stream_geometries(self, cfg=None):
        """Per-stream :class:`repro.core.placement.StreamGeometry` —
        the DRAM shape of this table's pools.

        A ``("groups", i)`` stream's page id indexes ``n_groups``
        stacked per-layer pool pages at once (``init_paged_cache``
        broadcasts the group's layers over one leading axis), so its
        placement page carries the group's whole stack of bytes.

        ``cfg`` overrides the model config for sizing (e.g. the full
        arch while the engine serves the smoke twin); it must share the
        smoke config's attn_pattern/pattern_tail structure or the
        stream list would not line up.
        """
        from repro.core.placement import StreamGeometry

        mcfg = self.cfg if cfg is None else cfg
        if cfg is not None and (
                tuple(mcfg.attn_pattern) != tuple(self.cfg.attn_pattern)
                or tuple(mcfg.pattern_tail) != tuple(self.cfg.pattern_tail)):
            raise ValueError(
                f"stream_geometries: override config {mcfg.name!r} has "
                f"pattern {mcfg.attn_pattern}/{mcfg.pattern_tail} but the "
                f"table was built for {self.cfg.attn_pattern}/"
                f"{self.cfg.pattern_tail}")
        isz = self._ITEMSIZE[mcfg.dtype]
        geoms = []
        for si, st in enumerate(self.streams):
            if st.is_state:
                if st.kind == "ssm":
                    pb = ((mcfg.ssm_conv - 1) * mcfg.d_inner * isz
                          + mcfg.d_inner * mcfg.ssm_state * 4)
                else:   # rglru: f32 hidden state rides beside the conv tap
                    pb = ((mcfg.conv1d_width - 1) * mcfg.resolved_lru_width
                          * isz + mcfg.resolved_lru_width * 4)
            else:
                pb = (2 * self.page_size * mcfg.n_kv_heads
                      * mcfg.resolved_head_dim * isz)
            if st.where[0] == "groups":
                pb *= mcfg.n_groups
            geoms.append(StreamGeometry(
                name=self.stream_name(si), n_pages=st.n_pages,
                page_bytes=int(pb), shards=st.shards,
                reserved_per_shard=RESERVED_PAGES))
        return tuple(geoms)

    def slot_page_ids(self, slot: int) -> List[Tuple[int, Tuple[int, ...]]]:
        """Physical pages ``slot`` holds right now, per stream — the
        page set one decode step reads AND writes (allocate-on-write:
        a resident page exists only because the slot's context reaches
        into it, and the KV gather sweeps every resident page)."""
        out = []
        for si, st in enumerate(self.streams):
            held = st.slot_pages.get(slot)
            if held is None:
                continue
            pids = (held,) if st.is_state else tuple(held.values())
            if pids:
                out.append((si, pids))
        return out

    # ------------------------------------------------------------ jitted ops
    def _insert_fn(self, cache, one, slot, pages, blocks, zeros, dumps):
        """Scatter a prefilled batch-1 contiguous cache into this
        slot's freshly assigned pages.  ``pages`` mirrors the stream
        list: KV entries are ``[n_lp]`` int32 *write* page ids (-1 =
        this logical page gets no fresh write -> the scatter row is
        redirected to the slot's shard's DUMP), state entries are
        scalar int32 page ids.  ``blocks`` carries the block-table row
        per KV stream (-1 -> ZERO); it differs from ``pages`` exactly
        on prefix-sharing attach rows, whose block points at the shared
        page while the redundant prefill write lands in DUMP.  Without
        sharing ``blocks is pages`` and this is the original admit,
        bit for bit.  ``zeros`` / ``dumps`` are the per-stream
        reserved-page ids of the slot's shard, passed traced so one
        compile serves every slot."""
        for si, st in enumerate(self.streams):
            pc, oc = self._get(cache, st.where), self._get(one, st.where)
            grouped = st.where[0] == "groups"
            if st.is_state:
                pc = self._ins_state(pc, oc, slot, pages[si], grouped)
            else:
                pc = self._ins_kv(pc, oc, slot, pages[si], blocks[si],
                                  grouped, zeros[si], dumps[si])
            cache = self._replace(cache, st.where, pc)
        return cache

    def _ins_kv(self, pc: PagedKVCache, oc: KVCache, slot, pids, bids,
                grouped, zero, dump):
        P, L = pc.page_size, pc.cache_len
        n_lp = pids.shape[0]
        write_ids = jnp.where(pids < 0, dump, pids)
        pad = n_lp * P - L

        def scat(pool, rows):            # rows: [L, kvh, hd]
            src = jnp.pad(rows, ((0, pad), (0, 0), (0, 0)))
            return pool.at[write_ids].set(src.reshape(n_lp, P, -1))

        block_row = jnp.where(bids < 0, zero, bids)
        if grouped:
            kp = jax.vmap(scat)(pc.kp, oc.k[:, 0])
            vp = jax.vmap(scat)(pc.vp, oc.v[:, 0])
            block = pc.block.at[:, slot].set(block_row)
        else:
            kp = scat(pc.kp, oc.k[0])
            vp = scat(pc.vp, oc.v[0])
            block = pc.block.at[slot].set(block_row)
        return dataclasses.replace(
            pc, kp=kp, vp=vp, block=block,
            length=jnp.maximum(pc.length, oc.length))

    def _ins_state(self, pc, oc, slot, pid, grouped):
        if grouped:
            return dataclasses.replace(
                pc,
                conv_p=pc.conv_p.at[:, pid].set(oc.conv[:, 0]),
                h_p=pc.h_p.at[:, pid].set(oc.h[:, 0]),
                block=pc.block.at[:, slot].set(pid))
        return dataclasses.replace(
            pc,
            conv_p=pc.conv_p.at[pid].set(oc.conv[0]),
            h_p=pc.h_p.at[pid].set(oc.h[0]),
            block=pc.block.at[slot].set(pid))

    def _release_fn(self, cache, slot, dumps):
        """Point every block-table row of ``slot`` back at its shard's
        DUMP page (``dumps``: per-stream traced ids)."""
        for si, st in enumerate(self.streams):
            pc = self._get(cache, st.where)
            grouped = st.where[0] == "groups"
            if grouped:
                block = pc.block.at[:, slot].set(dumps[si])
            else:
                block = pc.block.at[slot].set(dumps[si])
            cache = self._replace(cache, st.where,
                                  dataclasses.replace(pc, block=block))
        return cache

    def _assign_fn(self, si, cache, slot, jdx, pid):
        """Assign a zeroed page to logical page ``jdx`` of ``slot``
        (decode growth: allocate-on-write at a page boundary)."""
        st = self.streams[si]
        pc = self._get(cache, st.where)
        if st.where[0] == "groups":
            pc = dataclasses.replace(
                pc,
                kp=pc.kp.at[:, pid].set(0),
                vp=pc.vp.at[:, pid].set(0),
                block=pc.block.at[:, slot, jdx].set(pid))
        else:
            pc = dataclasses.replace(
                pc,
                kp=pc.kp.at[pid].set(0),
                vp=pc.vp.at[pid].set(0),
                block=pc.block.at[slot, jdx].set(pid))
        return self._replace(cache, st.where, pc)

    def _fork_fn(self, si, cache, slot, src, dst, jdx):
        """Copy-on-write fork: duplicate shared page ``src`` into the
        freshly allocated ``dst`` and re-target this slot's block row —
        the only device traffic sharing adds (one page read + write per
        fork, which telemetry bills as the ``cow`` class)."""
        st = self.streams[si]
        pc = self._get(cache, st.where)
        if st.where[0] == "groups":
            pc = dataclasses.replace(
                pc,
                kp=pc.kp.at[:, dst].set(pc.kp[:, src]),
                vp=pc.vp.at[:, dst].set(pc.vp[:, src]),
                block=pc.block.at[:, slot, jdx].set(dst))
        else:
            pc = dataclasses.replace(
                pc,
                kp=pc.kp.at[dst].set(pc.kp[src]),
                vp=pc.vp.at[dst].set(pc.vp[src]),
                block=pc.block.at[slot, jdx].set(dst))
        return self._replace(cache, st.where, pc)

    def _attach_fn(self, cache, slot, args, zeros):
        """Admit a slot from already-resident content: KV entries of
        ``args`` are ``(block_row [n_lp] with -1 -> ZERO, length)`` —
        only the block table and the batch length high-water mark move,
        no page content is written; state entries are ``(pid, conv,
        h)`` restored from a host snapshot exactly like
        :meth:`restore` (state pages are never shared)."""
        for si, st in enumerate(self.streams):
            pc = self._get(cache, st.where)
            grouped = st.where[0] == "groups"
            if st.is_state:
                pid, conv, h = args[si]
                if grouped:
                    pc = dataclasses.replace(
                        pc,
                        conv_p=pc.conv_p.at[:, pid].set(conv),
                        h_p=pc.h_p.at[:, pid].set(h),
                        block=pc.block.at[:, slot].set(pid))
                else:
                    pc = dataclasses.replace(
                        pc,
                        conv_p=pc.conv_p.at[pid].set(conv),
                        h_p=pc.h_p.at[pid].set(h),
                        block=pc.block.at[slot].set(pid))
            else:
                bids, length = args[si]
                block_row = jnp.where(bids < 0, zeros[si], bids)
                if grouped:
                    block = pc.block.at[:, slot].set(block_row)
                else:
                    block = pc.block.at[slot].set(block_row)
                pc = dataclasses.replace(
                    pc, block=block,
                    length=jnp.maximum(pc.length, length))
            cache = self._replace(cache, st.where, pc)
        return cache

    def _fetch_kv_fn(self, si, cache, ids):
        st = self.streams[si]
        pc = self._get(cache, st.where)
        if st.where[0] == "groups":
            return pc.kp[:, ids], pc.vp[:, ids]
        return pc.kp[ids], pc.vp[ids]

    def _fetch_state_fn(self, si, cache, pid):
        st = self.streams[si]
        pc = self._get(cache, st.where)
        if st.where[0] == "groups":
            return pc.conv_p[:, pid], pc.h_p[:, pid]
        return pc.conv_p[pid], pc.h_p[pid]

    def _restore_fn(self, cache, slot, payload):
        """Write offloaded page contents into freshly assigned pages.
        ``payload`` mirrors the stream list: KV entries are
        ``(pids [n_rows], jdxs [n_rows], k_pages, v_pages)`` (pids
        already allocated), state entries ``(pid, conv, h)``."""
        for si, st in enumerate(self.streams):
            pc = self._get(cache, st.where)
            grouped = st.where[0] == "groups"
            if st.is_state:
                pid, conv, h = payload[si]
                if grouped:
                    pc = dataclasses.replace(
                        pc,
                        conv_p=pc.conv_p.at[:, pid].set(conv),
                        h_p=pc.h_p.at[:, pid].set(h),
                        block=pc.block.at[:, slot].set(pid))
                else:
                    pc = dataclasses.replace(
                        pc,
                        conv_p=pc.conv_p.at[pid].set(conv),
                        h_p=pc.h_p.at[pid].set(h),
                        block=pc.block.at[slot].set(pid))
            else:
                pids, jdxs, kpg, vpg = payload[si]
                if grouped:
                    pc = dataclasses.replace(
                        pc,
                        kp=pc.kp.at[:, pids].set(kpg),
                        vp=pc.vp.at[:, pids].set(vpg),
                        block=pc.block.at[:, slot, jdxs].set(pids))
                else:
                    pc = dataclasses.replace(
                        pc,
                        kp=pc.kp.at[pids].set(kpg),
                        vp=pc.vp.at[pids].set(vpg),
                        block=pc.block.at[slot, jdxs].set(pids))
            cache = self._replace(cache, st.where, pc)
        return cache

    # ----------------------------------------------------------- operations
    def _reserved_ids(self, slot: int):
        """Per-stream (zeros, dumps) traced scalars of ``slot``'s shard,
        for the jitted ops that re-target dead block rows."""
        g = self.shard_of(slot)
        zeros = tuple(jnp.asarray(st.zero(g), jnp.int32)
                      for st in self.streams)
        dumps = tuple(jnp.asarray(st.dump(g), jnp.int32)
                      for st in self.streams)
        return zeros, dumps

    def admit(self, cache, one, slot: int, plen: int,
              keys: Optional[PrefixKeys] = None):
        """Allocate pages (from ``slot``'s shard extent) for a freshly
        prefilled request and scatter its contiguous batch-1 cache into
        them.

        With ``keys`` (prefix sharing), each prompt page first probes
        the shard's content registry: a hit attaches the live shared
        page (block row points at it, refcount +1, the redundant
        prefill write for that row lands in DUMP); a miss allocates as
        before and registers the fresh page under its content key.
        ``keys=None`` is the original allocator, bit for bit."""
        g = self.shard_of(slot)
        pages, blocks = [], []
        adm = {"attached_pages": 0, "registered_pages": 0,
               "attached_layer_tokens": 0, "total_layer_tokens": 0}
        for st in self.streams:
            if st.is_state:
                pid = st.free[g].pop()
                st.slot_pages[slot] = pid
                pages.append(jnp.asarray(pid, jnp.int32))
                blocks.append(pages[-1])
                continue
            need = self.kv_pages_for(plen, st)
            layers = self._stream_layers(st)
            ok_share = keys is not None and self._shareable(st, plen)
            held: Dict[int, int] = {}
            vec = np.full((st.n_lp,), -1, np.int32)   # write ids
            bvec = np.full((st.n_lp,), -1, np.int32)  # block rows
            for j in range(need):
                ptoks = (min(plen, (j + 1) * self.page_size)
                         - j * self.page_size)
                adm["total_layer_tokens"] += ptoks * layers
                key = self._page_key(keys, j, plen) if ok_share else None
                hit = st.shared[g].get(key) if key is not None else None
                if hit is not None:
                    st.ref[hit] += 1
                    held[j] = hit
                    bvec[j] = hit
                    adm["attached_pages"] += 1
                    adm["attached_layer_tokens"] += ptoks * layers
                    self.stats["pages_attached"] += 1
                else:
                    pid = st.free[g].pop()
                    held[j] = pid
                    vec[j] = pid
                    bvec[j] = pid
                    if key is not None:
                        self._register(st, g, key, pid)
                        adm["registered_pages"] += 1
            st.slot_pages[slot] = held
            pages.append(jnp.asarray(vec))
            blocks.append(jnp.asarray(bvec))
        self.last_admit = adm
        zeros, dumps = self._reserved_ids(slot)
        return self._insert_jit(cache, one, jnp.asarray(slot, jnp.int32),
                                tuple(pages), tuple(blocks), zeros, dumps)

    def state_snapshot(self, one) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
        """Host copy of the batch-1 prefill cache's recurrent state,
        keyed by stream index — what :meth:`admit_cached` writes back
        (state pages are never shared, so the full-prompt memo restores
        them through the same host round trip offload/restore uses)."""
        snap = {}
        for si, st in enumerate(self.streams):
            if not st.is_state:
                continue
            oc = self._get(one, st.where)
            grouped = st.where[0] == "groups"
            conv = oc.conv[:, 0] if grouped else oc.conv[0]
            h = oc.h[:, 0] if grouped else oc.h[0]
            snap[si] = jax.device_get((conv, h))
        return snap

    def admit_cached(self, cache, slot: int, plen: int, keys: PrefixKeys,
                     state_payload: Dict[int, Tuple[np.ndarray, np.ndarray]]):
        """Admit a whole prompt from resident shared pages — the
        full-skip path: every KV page of every stream attaches from the
        registry (no prefill ran; :meth:`can_admit_cached` must hold),
        recurrent state restores from ``state_payload`` (a
        :meth:`state_snapshot` taken when the prompt first prefilled).
        The KV length high-water mark is ``min(plen, cache_len)`` per
        stream, exactly what the skipped prefill's admit would have
        set."""
        g = self.shard_of(slot)
        args = []
        adm = {"attached_pages": 0, "registered_pages": 0,
               "attached_layer_tokens": 0, "total_layer_tokens": 0}
        for si, st in enumerate(self.streams):
            if st.is_state:
                if si not in state_payload:
                    raise PageTableError(
                        f"admit_cached: no state snapshot for stream "
                        f"{st.where} (kind={st.kind!r}) — the memo entry "
                        f"must carry every recurrent stream")
                pid = st.free[g].pop()
                st.slot_pages[slot] = pid
                conv, h = state_payload[si]
                args.append((jnp.asarray(pid, jnp.int32),
                             jnp.asarray(conv), jnp.asarray(h)))
                continue
            need = self.kv_pages_for(plen, st)
            layers = self._stream_layers(st)
            held: Dict[int, int] = {}
            bvec = np.full((st.n_lp,), -1, np.int32)
            for j in range(need):
                pid = st.shared[g][self._page_key(keys, j, plen)]
                st.ref[pid] += 1
                held[j] = pid
                bvec[j] = pid
                ptoks = (min(plen, (j + 1) * self.page_size)
                         - j * self.page_size)
                adm["attached_pages"] += 1
                adm["attached_layer_tokens"] += ptoks * layers
                adm["total_layer_tokens"] += ptoks * layers
                self.stats["pages_attached"] += 1
            st.slot_pages[slot] = held
            args.append((jnp.asarray(bvec),
                         jnp.asarray(min(plen, st.cache_len), jnp.int32)))
        self.stats["full_attaches"] += 1
        self.last_admit = adm
        zeros, _ = self._reserved_ids(slot)
        return self._attach_jit(cache, jnp.asarray(slot, jnp.int32),
                                tuple(args), zeros)

    def release(self, cache, slot: int):
        """Free a retired slot's pages; its block rows return to DUMP.
        A shared (registered) page only drops one reference — it frees
        when its last holder lets go."""
        g = self.shard_of(slot)
        for st in self.streams:
            held = st.slot_pages.pop(slot, None)
            if held is None:
                continue
            for pid in ([held] if st.is_state else held.values()):
                if pid in st.ref:
                    self._decref(st, g, pid)
                else:
                    st.free[g].append(pid)
        _, dumps = self._reserved_ids(slot)
        return self._release_jit(cache, jnp.asarray(slot, jnp.int32), dumps)

    def prepare_step(self, cache, slot: int, pos: int,
                     cow_events: Optional[List[Tuple[int, int]]] = None):
        """Ensure the page each KV stream will write at ``pos`` is
        assigned (from ``slot``'s shard extent) **and private** to this
        slot.  Returns ``(cache, ok)``; ``ok`` is False when a pool is
        exhausted (the engine must preempt a victim and retry).

        Copy-on-write: when the write lands in a *shared* page
        (refcount > 1) the slot forks — a fresh page is allocated, the
        shared content copied device-side, and the block row
        re-targeted; when the slot is the page's *sole* holder
        (refcount == 1) it simply unregisters the page in place and
        writes through, making every append/ring-wrap bit-identical to
        unshared serving.  Each fork appends ``(stream_index,
        layer_tokens_copied)`` to ``cow_events`` for telemetry.

        Invariant — *partial progress is committed*: page assignments
        (and forks) for streams visited before the exhausted one stay
        in the cache and in ``slot_pages`` even on the ``ok=False``
        return.  That is deliberate and safe: an assigned page is
        recorded under its ``jdx``, so the post-preemption retry skips
        it (a forked page is private, so the retry's ``ref`` probe
        skips it too) and only the still-missing streams act, and page
        content stays consistent until the decode step writes through
        the block table — generations are bit-identical to a serve
        that never exhausted the pool (``tests/test_paged_cache.py``
        pins this).  Callers must not assume the cache is untouched
        when ``ok`` is False."""
        g = self.shard_of(slot)
        for si, st in enumerate(self.streams):
            if st.is_state:
                continue
            jdx = (pos % st.cache_len) // self.page_size
            held = st.slot_pages[slot]
            pid = held.get(jdx)
            if pid is not None:
                if pid not in st.ref:
                    continue              # private page: write through
                if st.ref[pid] == 1:
                    self._unregister(st, pid)   # sole holder: take it
                    continue                    # private in place
                if not st.free[g]:
                    return cache, False
                dst = st.free[g].pop()
                st.ref[pid] -= 1
                held[jdx] = dst
                cache = self._fork_jit[si](
                    cache, jnp.asarray(slot, jnp.int32),
                    jnp.asarray(pid, jnp.int32), jnp.asarray(dst, jnp.int32),
                    jnp.asarray(jdx, jnp.int32))
                self.stats["cow_forks"] += 1
                spans.count("page_table.forks")
                if cow_events is not None:
                    cow_events.append(
                        (si, self.page_size * self._stream_layers(st)))
                continue
            if not st.free[g]:
                return cache, False
            pid = st.free[g].pop()
            held[jdx] = pid
            cache = self._assign_jit[si](
                cache, jnp.asarray(slot, jnp.int32),
                jnp.asarray(jdx, jnp.int32), jnp.asarray(pid, jnp.int32))
            spans.count("page_table.assigns")
        return cache, True

    def count_pages(self) -> None:
        """Add the KV pages live slots hold now (a shared page once: the
        pool less its free lists) and the pool's KV pages to the span
        counters ``page_table.pages_live`` and ``page_table.pages_pool``;
        the engine calls it once per decode step."""
        if not spans.recording():
            return
        kv = [st for st in self.streams if not st.is_state]
        pool = self.resident_pages * len(kv)
        spans.count("page_table.pages_live",
                    pool - sum(len(f) for st in kv for f in st.free))
        spans.count("page_table.pages_pool", pool)

    def offload(self, cache, slot: int, tokens: int):
        """Copy a slot's resident pages to host, free them on device.

        Returns ``(cache, payload)``.  The host copy is explicit
        (``jax.device_get`` into numpy), so the content round-trips
        through host memory, not a device alias.
        """
        g = self.shard_of(slot)
        kv, state = {}, {}
        for si, st in enumerate(self.streams):
            if slot not in st.slot_pages:
                raise PageTableError(
                    f"offload: slot {slot} holds no pages in stream "
                    f"{st.where} (kind={st.kind!r}); live slots there: "
                    f"{sorted(st.slot_pages)} — offload victims must be "
                    f"admitted slots")
            held = st.slot_pages.pop(slot)
            if st.is_state:
                conv, h = self._fetch_jit[si](cache, jnp.asarray(held, jnp.int32))
                state[si] = jax.device_get((conv, h))
                st.free[g].append(held)
            else:
                jdxs = sorted(held)
                ids = jnp.asarray([held[j] for j in jdxs], jnp.int32)
                kpg, vpg = self._fetch_jit[si](cache, ids)
                kv[si] = (dict(zip(jdxs, range(len(jdxs)))),
                          *jax.device_get((kpg, vpg)))
                # the host payload owns a private copy of shared pages,
                # so offload just drops this slot's references; restore
                # later allocates fresh private pages
                for pid in held.values():
                    if pid in st.ref:
                        self._decref(st, g, pid)
                    else:
                        st.free[g].append(pid)
        _, dumps = self._reserved_ids(slot)
        cache = self._release_jit(cache, jnp.asarray(slot, jnp.int32), dumps)
        return cache, PagePayload(kv=kv, state=state, tokens=int(tokens))

    def can_restore(self, payload: PagePayload, slot: int) -> bool:
        """Whether ``slot``'s shard has pages for the payload in every
        stream (restore allocates strictly shard-locally, like admit)."""
        g = self.shard_of(slot)
        need = payload.pages_needed()
        for si, st in enumerate(self.streams):
            if len(st.free[g]) < (1 if st.is_state else need[si]):
                return False
        return True

    def restore(self, cache, slot: int, payload: PagePayload):
        """Re-admit an offloaded slot: new pages (from ``slot``'s shard
        extent — any slot/shard, not necessarily the original), same
        bytes."""
        g = self.shard_of(slot)
        args = []
        for si, st in enumerate(self.streams):
            if st.is_state:
                pid = st.free[g].pop()
                st.slot_pages[slot] = pid
                conv, h = payload.state[si]
                args.append((jnp.asarray(pid, jnp.int32),
                             jnp.asarray(conv), jnp.asarray(h)))
            else:
                jdx_rows, kpg, vpg = payload.kv[si]
                jdxs = list(jdx_rows)
                pids = [st.free[g].pop() for _ in range(len(jdxs))]
                st.slot_pages[slot] = dict(zip(jdxs, pids))
                args.append((jnp.asarray(pids, jnp.int32),
                             jnp.asarray(jdxs, jnp.int32),
                             jnp.asarray(kpg), jnp.asarray(vpg)))
        return self._restore_jit(cache, jnp.asarray(slot, jnp.int32),
                                 tuple(args))


# ---------------------------------------------------------------------------
# Test/debug helper
# ---------------------------------------------------------------------------
def logical_view(cache):
    """Resolve a paged cache pytree into the contiguous cache pytree a
    ``model.init_cache`` decode would carry (KVCache/SSMCache/RGLRUCache
    with the same ``{'groups', 'tail'}`` structure).

    The paged==contiguous equivalence suite compares this view bitwise
    against the contiguous engine's cache: values must land in the same
    slot order for attention to be bit-identical.
    """
    def one(node):
        if isinstance(node, PagedKVCache):
            if node.block.ndim == 3:      # grouped: [G, ...] leaves
                k, v = jax.vmap(
                    lambda kp, vp, blk: paged_kv_view(
                        dataclasses.replace(node, kp=kp, vp=vp, block=blk))
                )(node.kp, node.vp, node.block)
            else:
                k, v = paged_kv_view(node)
            return KVCache(k=k, v=v, length=node.length)
        if isinstance(node, PagedSSMCache):
            if node.block.ndim == 2:
                return SSMCache(
                    conv=jax.vmap(lambda c, b: c[b])(node.conv_p, node.block),
                    h=jax.vmap(lambda h, b: h[b])(node.h_p, node.block))
            return SSMCache(conv=node.conv_p[node.block],
                            h=node.h_p[node.block])
        if isinstance(node, PagedRGLRUCache):
            if node.block.ndim == 2:
                return RGLRUCache(
                    conv=jax.vmap(lambda c, b: c[b])(node.conv_p, node.block),
                    h=jax.vmap(lambda h, b: h[b])(node.h_p, node.block))
            return RGLRUCache(conv=node.conv_p[node.block],
                              h=node.h_p[node.block])
        return node

    return {
        top: tuple(one(node) for node in cache[top])
        for top in ("groups", "tail")
    }
