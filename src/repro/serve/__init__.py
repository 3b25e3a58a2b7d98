"""Serving stack: sharded step builders, the continuous-batching engine,
paged cache management, and RTC traffic telemetry.

From the entry point down:

* ``engine`` — :class:`ServeEngine` owns the compiled programs
  (length-bucketed masked prefill, per-slot-position decode, unified
  per-request sampling) and the cache layout; ``serve()`` checks its
  arguments and hands the requests to
* ``scheduler`` — a :class:`~repro.serve.scheduler.Scheduler` owns one
  ``serve()`` call's state (the cache, the slots, the pending and
  suspended queues, the first tokens still on the device, the prompt
  memo) and runs admission, page growth, preemption and one decode
  step at a time over
* ``paging`` — the cache residency (block tables, page pools, host
  offload) — and the engine's decode step.

``telemetry`` owns the accounting (engine events -> DRAM bytes ->
:class:`repro.core.workload.WorkloadProfile`), which is how serving
traffic reaches the paper's RTC policy engine; its ``TelemetryHooks``
states the events a sink may take.

Paged-cache design note (PR 4)
------------------------------
The contiguous engine gave every batch slot a ``max_len``-row cache
allocation for its whole lifetime — long contexts were rejected at
admission and cold KV occupied hot accelerator memory.  The paged mode
(``ServeEngine(..., paged=PagedCacheConfig(...))``) replaces that with
block-table paging, chosen as follows:

* **Page size** — ``page_size`` tokens of K+V per attention layer (one
  pool per attention pattern position; recurrent ssm/rglru state and
  conv tails are one *state page* per slot in mirror pools, so all 10
  architectures go through one :class:`~repro.serve.paging.PageTable`).
  A page is simultaneously the allocation quantum, the offload-transfer
  quantum, and — for ``rtc.evaluate`` — the DRAM-row mapping quantum
  (PENDRAM's point: how logical rows land on physical rows is a policy
  axis; the page table is that policy made explicit).  Logical layouts
  equal the contiguous cache's ring/append order, so paged decode is
  *bit-identical* to contiguous decode (pinned across all 10 archs in
  ``tests/test_paged_cache.py``).
* **Capacity vs. residency** — a slot's logical capacity is ``max_ctx``
  (may exceed ``max_len``: decode grows the slot's page list
  allocate-on-write, so prompt+generation can outlive the old
  contiguous cap), while device residency is bounded by
  ``resident_pages`` per KV stream.
* **Eviction policy** — when a pool runs dry the engine preempts the
  *newest* live request (highest request id; the oldest admitted slot
  is only victimized by its own elders, which preserves FCFS progress),
  offloads its pages to host memory via ``jax.device_put``, and resumes
  it FIFO — before any new admission — once a slot and pages free up.
  Restores are bit-exact: pages re-enter different physical pool pages,
  the block table re-targets, content and the continued generation are
  unchanged.
* **Offload traffic accounting** — every offload/restore is a telemetry
  event (``record_page_out`` / ``record_page_in``); whole-page bytes
  (context rounded up per layer, plus state pages) join weight/KV/state
  traffic in ``workload.from_decode`` as extra DRAM reads/writes, so
  the RTC savings number sees exactly the traffic the refresh model
  cares about.  The invariant "summed per-event bytes == profile x
  steps" is pinned in ``tests/test_paged_cache.py``.
* **Decode backend** (PR 5) — ``ServeEngine(decode_backend=
  "pallas_paged")`` swaps the gather path (materialize the contiguous
  logical view each step) for the block-table Pallas kernel
  (:mod:`repro.kernels.paged_attention` — design note in the
  ``repro.kernels`` package docstring) that reads K/V pages in place.
  Generations are identical either way; telemetry accounts the gather
  path's phantom view traffic and the kernel path's true per-page
  reads, which is where the RTC energy delta between the two shows up.

Prefix-sharing / copy-on-write design note (PR 10)
--------------------------------------------------
``PagedCacheConfig(sharing=PrefixSharingConfig(...))`` turns the page
table content-addressed, ROMANet-style reuse applied at the serving
layer (ROADMAP item 2): identical prompt prefixes map to the *same*
physical KV pages, so N same-prefix requests allocate the prefix once.

* **Hash scheme** — vLLM-style chained content hashing
  (:func:`~repro.serve.paging.prefix_page_keys`): page ``j``'s key is
  ``sha1(key_{j-1} || tokens[jP:(j+1)P])`` seeded with a version tag,
  so a page's identity covers its whole prefix, not just its own
  tokens; a ragged tail gets a ``tail``-salted key and the whole-prompt
  key addresses the full-skip memo.  Keys are per stream and per PR 8
  shard — registries live inside each stream's per-shard extent, so
  sharing never crosses a device boundary.
* **Refcount lifecycle** — a keyed page registers at admission with
  refcount 1; a later admission whose page key is already registered
  *attaches* (refcount += 1) instead of allocating, and its prefill
  scatter is redirected to the DUMP row (the compute still runs — that
  is what keeps shared serving bit-identical; the saving is the page
  row set, which telemetry books as the ``prefix_hit`` class and the
  trace path sees as per-step page-id dedup).  Release/offload
  decrement; the page frees and unregisters at zero.  Sharing is
  in-flight only: no pages outlive their last referencing request.
* **Fork-on-write rules** — decode's ``prepare_step`` never appends
  into a page the slot holds a *shared* reference to: refcount > 1
  forks (allocate + on-device page copy + block-table retarget +
  decref), refcount == 1 unregisters in place and writes through.
  Fork allocation failure feeds the existing preempt/retry path, and
  the sole-live-slot deadlock bound is preserved (a lone slot's refs
  are all its own, so it never needs a fork page).  Recurrent *state*
  pages are rewritten every step and therefore never shared.
* **Scheduler policy** — ``schedule="prefix"`` groups the admission
  queue by whole-prefix group key (first-arrival group order, so no
  starvation) to co-schedule same-prefix requests while their pages
  are live; generations are bit-independent of the schedule because
  sampling keys are (request id, token index)-addressed.
* **Full skip** — an exact whole-prompt hit on the bounded memo skips
  prefill entirely (attach every page, restore the host state
  snapshot, replay the memoized logits — bit-exact).
"""
from repro.serve.engine import (PrefillBuckets, Request, ServeEngine,
                                build_decode_step, build_prefill_step,
                                cache_specs)
from repro.serve.paging import (PagedCacheConfig, PageTable, PrefixKeys,
                                PrefixSharingConfig, logical_view,
                                prefix_page_keys)
from repro.serve.telemetry import ServeTelemetry, TrafficModel

__all__ = ["PrefillBuckets", "Request", "ServeEngine", "build_decode_step",
           "build_prefill_step", "cache_specs", "PagedCacheConfig",
           "PageTable", "PrefixKeys", "PrefixSharingConfig", "logical_view",
           "prefix_page_keys", "ServeTelemetry", "TrafficModel"]
