"""Serving telemetry: account engine DRAM traffic for the RTC engine.

The paper's closing argument is that RTC applies to any workload whose
DRAM access pattern stays predictable over a retention window — an LM
decode loop is exactly that (every step re-streams the active weights
and sweeps the KV cache in order).  This module closes the loop between
the serving stack and the energy model: the engine reports *events*
(one prefill of ``plen`` tokens; one decode step over live contexts),
:class:`TrafficModel` converts them to bytes for a target deployment
config, and :meth:`ServeTelemetry.workload_profile` folds the result
into a :class:`repro.core.workload.WorkloadProfile` that
``repro.core.rtc.evaluate`` / ``repro.core.refresh_sim.simulate``
consume directly.

Splitting events from byte constants means the *scheduling trace* can
come from a real (smoke-scale) engine run while the *byte magnitudes*
come from the full-size deployment config — the traffic pattern is
measured, not hand-built, and the energy numbers still describe the
production model.

Paged serving adds a third traffic class: page-out/page-in events
(host offload of a preempted slot's cache pages and their restore —
:mod:`repro.serve.paging`) convert to whole-page bytes via
:meth:`TrafficModel.page_bytes` and join the profile as extra DRAM
reads/writes.  All byte accumulators are exact ints, so the invariant
"summed per-event bytes == profile x decode steps" holds bit-for-bit
(test-pinned in ``tests/test_paged_cache.py``).

Decode-backend awareness (PR 5): how a paged step's KV bytes move
depends on how attention resolves the block tables, and the engine
reports its backend through :meth:`ServeTelemetry.configure_decode`:

* ``gather`` — the jnp path *materializes* the contiguous logical view
  each step: every block-table page is read and a full cache-length
  copy is written per attention layer per live slot, **regardless of
  context occupancy**, before attention even sweeps the view.  That
  phantom traffic (:meth:`TrafficModel.gather_view_read_bytes` /
  ``gather_view_write_bytes``) is exactly the avoidable copy the
  paper's access-management argument targets, and it is accounted so
  the RTC number sees it.
* ``pallas_paged`` — the kernel reads pages in place: the KV sweep is
  ``ceil(ctx/page_size)`` whole pages per layer
  (:meth:`TrafficModel.kv_page_read_bytes`) and nothing else — no
  materialized-view traffic, which is the point of the kernel.
* ``contiguous`` (no paging) — row-exact sweep of the live context,
  unchanged from the seed accounting.

Prefix sharing (PR 10) adds a fourth class.  When admission attaches
registry pages instead of scattering fresh content
(:mod:`repro.serve.paging`), :meth:`ServeTelemetry.record_admit_shared`
splits the admission's KV bytes into *hit* (layer-tokens served by
already-resident shared pages — admission work avoided) and *written*
(the novel remainder plus the always-private recurrent state), with the
exact-int invariant ``hit + written == unshared total`` per admission
(test-pinned in ``tests/test_prefix_sharing.py``).  Copy-on-write forks
are the only device traffic sharing *adds*: each fork bills one page
read + one page write (:meth:`ServeTelemetry.record_cow`), and those
``cow`` bytes join the workload profile's KV streams so the RTC number
never flatters sharing.  Hit bytes stay *out* of the profile: the
dedup-attach admission still physically scatters its redundant rows
into the DUMP page, so the saving is realized as a smaller live row set
(the trace/placement path bills it), while full-skip admissions avoid
the prefill compute outright and are counted in
``prefix_full_skips``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from repro.core.workload import WorkloadProfile, from_decode
from repro.models.config import ModelConfig

__all__ = ["TrafficModel", "ServeTelemetry", "TelemetryHooks"]

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class TrafficModel:
    """Per-event DRAM byte constants of one model deployment.

    ``kv_caps`` / ``kv_token_bytes`` carry one entry per attention layer
    (cache slots, K+V bytes per cached token); recurrent (ssm/rglru)
    layers contribute ``state_bytes`` of O(1) per-slot state that is
    read *and* written every step.  ``page_size`` (tokens per KV page,
    0 = contiguous cache) makes offload traffic page-granular: a slot's
    pages cover its context rounded up per layer, exactly what the
    engine moves on preemption.
    """

    param_bytes: int            # resident weight bytes (footprint share)
    param_read_bytes: int       # active weight bytes streamed per step
    kv_caps: Tuple[int, ...]
    kv_token_bytes: Tuple[int, ...]
    state_bytes: int
    page_size: int = 0

    @classmethod
    def from_config(cls, cfg: ModelConfig, max_len: int,
                    page_size: int = 0) -> "TrafficModel":
        itemsize = _ITEMSIZE[cfg.dtype]
        counts = cfg.param_counts()
        caps, bpt = [], []
        state = 0
        for i in range(cfg.n_layers):
            kind = cfg.layer_kind(i)
            if kind in ("global", "local"):
                caps.append(cfg.decode_cache_len(kind, max_len))
                bpt.append(2 * cfg.n_kv_heads * cfg.resolved_head_dim
                           * itemsize)
            elif kind == "ssm":
                state += ((cfg.ssm_conv - 1) * cfg.d_inner * itemsize
                          + cfg.d_inner * cfg.ssm_state * 4)   # h is f32
            elif kind == "rglru":
                dl = cfg.resolved_lru_width
                state += (cfg.conv1d_width - 1) * dl * itemsize + dl * 4
        return cls(
            param_bytes=counts["total"] * itemsize,
            param_read_bytes=cfg.active_param_counts() * itemsize,
            kv_caps=tuple(caps),
            kv_token_bytes=tuple(bpt),
            state_bytes=state,
            page_size=int(page_size),
        )

    # ------------------------------------------------------------ per event
    @property
    def cache_slot_bytes(self) -> int:
        """Allocated decode-cache bytes per batch slot."""
        return sum(c * b for c, b in zip(self.kv_caps, self.kv_token_bytes)) \
            + self.state_bytes

    def kv_read_bytes(self, ctx: int) -> int:
        """KV bytes one slot with ``ctx`` cached tokens reads per step."""
        return sum(min(ctx, c) * b
                   for c, b in zip(self.kv_caps, self.kv_token_bytes))

    @property
    def kv_page_token_bytes(self) -> int:
        """K+V bytes of ONE cached token in ONE attention layer —
        uniform across layers (KV heads and head_dim do not vary per
        layer), so it is the single conversion constant for the
        prefix-sharing *layer-token* accounting
        (:meth:`ServeTelemetry.record_admit_shared`)."""
        return self.kv_token_bytes[0] if self.kv_token_bytes else 0

    @property
    def kv_write_bytes(self) -> int:
        """KV bytes one slot appends per step (one token per layer)."""
        return sum(self.kv_token_bytes)

    def page_bytes(self, ctx: int) -> int:
        """Bytes one offload/restore of a ``ctx``-token slot moves:
        every layer's resident pages (context rounded up to whole pages,
        capped at the layer's cache length) plus the recurrent state
        pages.  With ``page_size == 0`` the move is row-exact."""
        p = self.page_size
        total = self.state_bytes
        for c, b in zip(self.kv_caps, self.kv_token_bytes):
            rows = min(ctx, c)
            if p:
                rows = -(-rows // p) * p
            total += rows * b
        return total

    def kv_page_read_bytes(self, ctx: int) -> int:
        """KV bytes one slot's *kernel* decode step reads: whole pages
        covering the live context per layer (the block-table index map
        DMAs page granules; the partial tail page still streams its
        full ``page_size`` rows).  Row-exact when ``page_size == 0``."""
        p = self.page_size
        if not p:
            return self.kv_read_bytes(ctx)
        return sum((-(-min(ctx, c) // p) * p) * b
                   for c, b in zip(self.kv_caps, self.kv_token_bytes))

    @property
    def gather_view_read_bytes(self) -> int:
        """Pool bytes one slot's *gather* decode step reads to
        materialize the logical view: every block-table page of every
        attention layer — ``ceil(cache_len/page_size)`` full pages —
        independent of how much context is actually live."""
        p = self.page_size
        if not p:
            return sum(c * b for c, b in
                       zip(self.kv_caps, self.kv_token_bytes))
        return sum((-(-c // p) * p) * b
                   for c, b in zip(self.kv_caps, self.kv_token_bytes))

    @property
    def gather_view_write_bytes(self) -> int:
        """Bytes the materialized contiguous view costs to write per
        slot per gather step.  The lowered computation gathers *whole
        pages* — ``ceil(cache_len/page_size) * page_size`` rows per
        layer — and only then slices to the logical cache length, so
        the written copy is page-granular (the jaxpr-level accounting
        the static auditor cross-checks; the previous row-sliced count
        under-billed the tail page)."""
        p = self.page_size
        if not p:
            return sum(c * b for c, b in
                       zip(self.kv_caps, self.kv_token_bytes))
        return sum((-(-c // p) * p) * b
                   for c, b in zip(self.kv_caps, self.kv_token_bytes))

    # -------------------------------------------------- per-class breakdown
    #: Traffic classes of one decode step, the shared vocabulary of
    #: telemetry and the jaxpr-level auditor (``repro.analysis``).
    DECODE_CLASSES = ("kv_sweep_read", "kv_page_read", "kv_append_write",
                      "state_read", "state_write",
                      "gather_view_read", "gather_view_write")

    def static_decode_classes(self, ctx_lengths: Sequence[int],
                              mode: str) -> dict:
        """Exact per-class bytes of ONE decode step over live slots with
        the given context lengths, keyed by :attr:`DECODE_CLASSES`.

        This is the analytic twin of the static traffic auditor: at
        full occupancy (every slot at its layer cache length) the
        structural byte count of the lowered decode step equals this
        breakdown class-for-class, which ``repro.analysis`` asserts.
        :meth:`ServeTelemetry.record_decode` accumulates through the
        same method, so the runtime accounting cannot drift from the
        statically-verified one.
        """
        live = len(ctx_lengths)
        cls = {k: 0 for k in self.DECODE_CLASSES}
        cls["state_read"] = self.state_bytes * live
        cls["state_write"] = self.state_bytes * live
        cls["kv_append_write"] = self.kv_write_bytes * live
        if mode == "pallas_paged":
            cls["kv_page_read"] = sum(self.kv_page_read_bytes(c)
                                      for c in ctx_lengths)
        else:
            cls["kv_sweep_read"] = sum(self.kv_read_bytes(c)
                                       for c in ctx_lengths)
        if mode == "gather":
            cls["gather_view_read"] = self.gather_view_read_bytes * live
            cls["gather_view_write"] = self.gather_view_write_bytes * live
        return cls


def _ignore(*args, **kwargs) -> None:
    """A hook the sink does not have."""


class TelemetryHooks:
    """The serve loop's telemetry protocol, over any sink or ``None``.

    :meth:`ServeEngine.serve <repro.serve.engine.ServeEngine.serve>`
    wraps its ``telemetry`` argument in one of these, and the scheduler
    calls the hooks on it unconditionally.  A sink may have any subset
    of them; a hook it lacks is a no-op here:

    * ``configure_decode(backend, paged)``: once a call, before any
      request runs: the engine's ``decode_backend`` and whether its
      cache is paged;
    * ``record_prefill(plen, dt, padded_len)``: a prefilled admission's
      first token reached the host; ``plen`` true prompt tokens,
      ``dt`` the ``serve.admit`` span's seconds, ``padded_len`` the
      prefill bucket;
    * ``record_decode(ctx_lengths, dt)``: one decode step over the live
      slots' contexts; ``dt`` the ``serve.decode`` plus
      ``serve.token_pull`` spans' seconds;
    * ``record_page_out(ctx)``, ``record_page_in(ctx)``: a slot holding
      ``ctx`` tokens offloaded to the host, or restored;
    * ``record_admit_shared(plen, hit_layer_tokens, total_layer_tokens,
      skipped_prefill=False)``: an admission under prefix sharing;
    * ``record_cow(layer_tokens)``: one copy-on-write fork.

    ``trace`` is the sink's :class:`repro.core.trace.PageAccessTrace`,
    which a paged engine appends each decode step's page accesses to,
    or ``None`` when the sink has none.  :class:`ServeTelemetry` has
    every hook.
    """

    HOOKS = ("configure_decode", "record_prefill", "record_decode",
             "record_page_out", "record_page_in", "record_admit_shared",
             "record_cow")
    __slots__ = HOOKS + ("trace",)

    def __init__(self, sink=None):
        for name in self.HOOKS:
            setattr(self, name, getattr(sink, name, _ignore))
        self.trace = getattr(sink, "trace", None)


class ServeTelemetry:
    """Accumulates engine events and emits the RTC workload profile.

    ``ctx_scale`` linearly extrapolates the recorded per-slot context
    lengths before byte conversion (each layer still caps at its cache
    length).  Use it when the scheduling trace comes from a downsized
    engine (e.g. a CPU smoke run with ``max_len=32``) but the profile
    should describe a deployment context: ``ctx_scale = serve_ctx /
    engine.max_len`` maps the measured occupancy shape onto the target
    context without hand-building the traffic.

    ``decode_mode`` — how decode-step KV bytes are converted:
    ``"contiguous"`` (row-exact sweep of the live context),
    ``"gather"`` (adds the paged gather path's materialized-view
    traffic), or ``"pallas_paged"`` (whole-page reads only — the
    kernel path never materializes a view).  ``None`` (default) lets
    the engine set it via :meth:`configure_decode` at serve time;
    passing an explicit mode pins it (the engine will not override).

    ``trace`` — an optional :class:`repro.core.trace.PageAccessTrace`
    the engine appends per-step page accesses to (paged engines only;
    the engine validates the stream binding at serve time).  Telemetry
    itself never reads it: it is the hand-off point between the serving
    loop and the trace-driven refresh simulation
    (:func:`repro.core.refresh_sim.simulate_trace`).
    """

    _MODES = ("contiguous", "gather", "pallas_paged")

    def __init__(self, traffic: TrafficModel, ctx_scale: float = 1.0,
                 decode_mode: Optional[str] = None, trace=None):
        if decode_mode is not None and decode_mode not in self._MODES:
            raise ValueError(
                f"decode_mode must be one of {self._MODES}, "
                f"got {decode_mode!r}")
        self.trace = trace
        self.traffic = traffic
        self.ctx_scale = float(ctx_scale)
        self._pinned_mode = decode_mode is not None
        self.decode_mode = decode_mode or "contiguous"
        self.n_prefills = 0
        self.prefill_tokens = 0         # TRUE prompt tokens prefetched
        self.prefill_padded_tokens = 0  # positions incl. bucket padding
        self.prefill_time_s = 0.0
        self.decode_steps = 0
        self.decode_time_s = 0.0
        self.tokens_generated = 0
        self.max_live = 0
        self.page_outs = 0             # slot offloads (device -> host)
        self.page_ins = 0              # slot restores (host -> device)
        # Byte totals are kept as exact ints so the invariant
        # "sum(per-event bytes) == profile * decode_steps" is testable
        # bit-for-bit (floats would round on the way in).
        self.param_read_bytes_total = 0  # active weights streamed per step
        self.kv_read_bytes_total = 0     # KV sweeps + recurrent state reads
        self.write_bytes_total = 0       # KV appends + recurrent state writes
        self.page_out_bytes_total = 0    # offloaded page bytes (DRAM reads)
        self.page_in_bytes_total = 0     # restored page bytes (DRAM writes)
        self.gather_read_bytes_total = 0   # phantom view gathers (reads)
        self.gather_write_bytes_total = 0  # phantom view copies (writes)
        # Prefix-sharing accounting (all zero unless the engine serves
        # with PrefixSharingConfig): per-admission hit/written split
        # plus the copy-on-write fork traffic — the only bytes sharing
        # ADDS to the device.
        self.prefix_admits = 0           # admissions that touched keys
        self.prefix_full_skips = 0       # whole-prompt memo admissions
        self.prefix_hit_tokens = 0       # layer-tokens attached, not written
        self.prefix_hit_bytes_total = 0    # hit layer-tokens as KV bytes
        self.admit_write_bytes_total = 0   # novel admission KV+state bytes
        self.cow_read_bytes_total = 0      # fork page copies (DRAM reads)
        self.cow_write_bytes_total = 0     # fork page copies (DRAM writes)

    def configure_decode(self, backend: str, paged: bool) -> None:
        """Engine hook: map its (decode_backend, paged?) pair onto the
        accounting mode.  A mode passed to the constructor is pinned
        and wins; otherwise contiguous engines are row-exact and paged
        engines account their backend's real traffic."""
        if self._pinned_mode:
            return
        self.decode_mode = backend if paged else "contiguous"

    # ------------------------------------------------------------- recording
    def record_prefill(self, plen: int, dt: float = 0.0,
                       padded_len: Optional[int] = None) -> None:
        """One prefill of ``plen`` TRUE prompt tokens.

        ``padded_len``: the bucket size actually lowered (>= plen) when
        the engine length-buckets prefill.  Traffic and the RTC profile
        are always accounted from ``plen`` — padding is compute the
        model masks out, not DRAM-resident prompt state — while the
        padded total is kept so the pad overhead stays visible
        (:attr:`prefill_pad_waste`).
        """
        self.n_prefills += 1
        self.prefill_tokens += int(plen)
        self.prefill_padded_tokens += int(plen if padded_len is None
                                          else padded_len)
        self.prefill_time_s += dt
        self.tokens_generated += 1   # first token samples off prefill logits

    @property
    def prefill_pad_waste(self) -> float:
        """Fraction of prefilled positions that were bucket padding."""
        if not self.prefill_padded_tokens:
            return 0.0
        return 1.0 - self.prefill_tokens / self.prefill_padded_tokens

    def record_decode(self, ctx_lengths: Sequence[int], dt: float = 0.0) -> None:
        """One batched decode step over live slots with the given
        per-slot context lengths (cached tokens attended).

        KV bytes follow :attr:`decode_mode`: the kernel path reads
        whole pages covering each live context and nothing more; the
        gather path additionally pays the materialized logical view
        (full block-table read + contiguous copy per layer per slot)
        on top of its row-exact attention sweep."""
        t = self.traffic
        live = len(ctx_lengths)
        self.decode_steps += 1
        self.decode_time_s += dt
        self.tokens_generated += live
        self.max_live = max(self.max_live, live)
        self.param_read_bytes_total += t.param_read_bytes
        # one source of truth: the same per-class breakdown the static
        # auditor (repro.analysis) verifies against the lowered jaxpr
        cls = t.static_decode_classes(
            [self._scaled(c) for c in ctx_lengths], self.decode_mode)
        self.kv_read_bytes_total += (cls["state_read"]
                                     + cls["kv_sweep_read"]
                                     + cls["kv_page_read"])
        self.write_bytes_total += (cls["kv_append_write"]
                                   + cls["state_write"])
        self.gather_read_bytes_total += cls["gather_view_read"]
        self.gather_write_bytes_total += cls["gather_view_write"]

    def _scaled(self, ctx: int) -> int:
        return int(round(ctx * self.ctx_scale))

    def record_admit_shared(self, plen: int, hit_layer_tokens: int,
                            total_layer_tokens: int,
                            skipped_prefill: bool = False) -> None:
        """One prefix-aware admission, split hit vs written.

        ``hit_layer_tokens`` — (layer, token) cells served by attaching
        already-resident shared pages; ``total_layer_tokens`` — the
        cells the same admission writes without sharing (the
        :attr:`PageTable.last_admit <repro.serve.paging.PageTable>`
        pair).  Bytes are exact ints off
        :attr:`TrafficModel.kv_page_token_bytes`, and per admission
        ``hit_bytes + written_bytes == total_layer_tokens *
        kv_page_token_bytes + state_bytes`` — the unshared admission
        total — holds by construction (the exact-sum invariant the
        tests pin; recurrent state is always written, never shared).

        ``skipped_prefill`` marks a full-prompt memo admission: no
        prefill executable ran, but the request still emits its first
        token off the memoized logits, so it accounts as one prefill
        event of ``plen`` true tokens with zero pad waste."""
        bpt = self.traffic.kv_page_token_bytes
        hit = int(hit_layer_tokens)
        total = int(total_layer_tokens)
        if hit > total:
            raise ValueError(
                f"record_admit_shared: hit_layer_tokens={hit} exceeds "
                f"total_layer_tokens={total}")
        self.prefix_admits += 1
        self.prefix_hit_tokens += hit
        self.prefix_hit_bytes_total += hit * bpt
        self.admit_write_bytes_total += ((total - hit) * bpt
                                         + self.traffic.state_bytes)
        if skipped_prefill:
            self.prefix_full_skips += 1
            self.n_prefills += 1
            self.prefill_tokens += int(plen)
            self.prefill_padded_tokens += int(plen)
            self.tokens_generated += 1

    def record_cow(self, layer_tokens: int) -> None:
        """One copy-on-write fork: ``layer_tokens`` (layer, token)
        cells copied device-side from the shared page into the private
        one — one whole-page read plus one whole-page write, unscaled
        (a fork moves exactly one page per stream at any context
        scale)."""
        b = int(layer_tokens) * self.traffic.kv_page_token_bytes
        self.cow_read_bytes_total += b
        self.cow_write_bytes_total += b

    @property
    def prefix_hit_frac(self) -> float:
        """Fraction of prefix-aware admission bytes served by shared
        pages (0.0 when sharing never engaged)."""
        denom = self.prefix_hit_bytes_total + self.admit_write_bytes_total
        if not denom:
            return 0.0
        return self.prefix_hit_bytes_total / denom

    def record_page_out(self, ctx: int) -> None:
        """One slot offload: its resident pages (a ``ctx``-token context)
        leave device DRAM for host memory."""
        self.page_outs += 1
        self.page_out_bytes_total += self.traffic.page_bytes(self._scaled(ctx))

    def record_page_in(self, ctx: int) -> None:
        """One slot restore: the offloaded pages stream back in."""
        self.page_ins += 1
        self.page_in_bytes_total += self.traffic.page_bytes(self._scaled(ctx))

    # ------------------------------------------------------------- reporting
    @property
    def decode_tok_per_s(self) -> float:
        if self.decode_time_s <= 0:
            return 0.0
        return (self.tokens_generated - self.n_prefills) / self.decode_time_s

    def workload_profile(self, name: str = "serve",
                         step_period_s: Optional[float] = None,
                         row_utilization: float = 1.0) -> WorkloadProfile:
        """Fold the recorded decode traffic into a `WorkloadProfile`.

        One profile iteration == one *mean* decode step of the recorded
        trace.  ``step_period_s`` overrides the measured mean step wall
        time (e.g. with a dry-run roofline bound when the trace was
        collected on a smoke model).
        """
        if self.decode_steps == 0:
            raise ValueError("no decode steps recorded")
        n = self.decode_steps
        period = step_period_s if step_period_s is not None \
            else self.decode_time_s / n
        if period <= 0:
            raise ValueError("step period must be positive")
        footprint = self.traffic.param_bytes \
            + self.max_live * self.traffic.cache_slot_bytes
        # gather-mode phantom traffic folds into the KV read/write
        # streams (the view copy moves through the same DRAM rows the
        # KV sweep walks); the split stays visible in the accumulators.
        # copy-on-write fork copies ride the KV streams too: sharing's
        # only added device traffic must reach the RTC number (hit
        # bytes do NOT join — dedup-attach realizes its saving through
        # the smaller live row set the trace/placement path bills)
        return from_decode(
            name,
            param_read_bytes=self.param_read_bytes_total / n,
            kv_read_bytes=(self.kv_read_bytes_total
                           + self.gather_read_bytes_total
                           + self.cow_read_bytes_total) / n,
            kv_write_bytes=(self.write_bytes_total
                            + self.gather_write_bytes_total
                            + self.cow_write_bytes_total) / n,
            page_out_bytes=self.page_out_bytes_total / n,
            page_in_bytes=self.page_in_bytes_total / n,
            footprint_bytes=footprint,
            step_period_s=period,
            row_utilization=row_utilization,
        )
