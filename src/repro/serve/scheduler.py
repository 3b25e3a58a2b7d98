"""The state of one :meth:`ServeEngine.serve` call, and its loop.

:class:`~repro.serve.engine.ServeEngine` owns the compiled programs
(prefill, the decode step, sampling keys and the sampler) and the cache
layout.  A :class:`Scheduler` owns one ``serve()`` call: the cache, the
batch slots and their host vectors, the pending and suspended requests,
the admissions whose first tokens are still on the device, and the
whole-prompt memo.  It runs the engine's programs and the engine's
:class:`~repro.serve.paging.PageTable` one decode step at a time.

Every engine program and page-table method is looked up on its
instance at call time (``self.engine.decode_step``,
``self.table.prepare_step``), so a wrapper set on the instance, such as
the benchmark's host spans, sees every call.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import spans

__all__ = ["Scheduler"]


class _Slot:
    """One admitted request: ``pos`` is the next decode position,
    ``emitted`` the tokens sampled so far, ``out`` the tokens.  A
    suspended request keeps its ``_Slot`` beside its host payload."""
    __slots__ = ("req", "pos", "emitted", "out")

    def __init__(self, req, pos: int, first_token: int):
        self.req = req
        self.pos = pos
        self.emitted = 1
        self.out = [first_token]


class Scheduler:
    """Continuous batching over the engine's ``max_batch`` slots for one
    ``serve()`` call: ``while sched.busy(): sched.step()``, then
    :meth:`finish` returns the outputs in input order.

    ``requests`` are the call's :class:`~repro.serve.engine.Request` s,
    ``order`` their input index; ``hooks`` the caller's sink as
    :class:`~repro.serve.telemetry.TelemetryHooks`; ``eos`` the call's
    end token or ``None``; ``t_entry`` the call's entry
    (``time.perf_counter_ns``), where each queue wait starts.
    """

    def __init__(self, engine, requests, hooks, seed: int,
                 eos: Optional[int], t_entry: int):
        self.engine = engine
        self.table = engine.page_table
        self.hooks = hooks
        # Page-access trace: page ids are page-table state, so recording
        # rides the host loop and adds nothing to the jitted steps.
        # Accesses between decode steps (admission scatters, restores,
        # offload reads) gather in `_pages` and fold into the next
        # step's record.
        self.trace = hooks.trace
        if self.trace is not None:
            if self.table is None:
                raise ValueError(
                    "telemetry.trace set but the engine is not paged — "
                    "page-access traces need a PageTable (pass "
                    "paged=PagedCacheConfig(...) at engine build)")
            names = self.table.stream_names()
            if tuple(self.trace.stream_names) != names:
                raise ValueError(
                    f"telemetry.trace streams {self.trace.stream_names} do "
                    f"not match this engine's page table streams {names}")
        self._pages: Dict[int, set] = {}
        self.eos = eos
        self.t_entry = t_entry
        self.base = jax.random.key(seed)
        vocab = engine.model.cfg.vocab_size
        self.use_top_k = any(r.top_k != vocab for r in requests)
        self.outputs: List[Optional[np.ndarray]] = [None] * len(requests)

        B = self.B = engine.max_batch
        self.cache = engine.new_cache()
        self.slots: List[Optional[_Slot]] = [None] * B
        # per slot: the token it feeds next, at position `pos`; its
        # request id and tokens emitted (the sampling key); its sampling
        # parameters
        self.tok = np.zeros((B,), np.int32)
        self.pos = np.zeros((B,), np.int32)
        self.rid = np.zeros((B,), np.int32)
        self.emit = np.zeros((B,), np.int32)
        self.temp = np.zeros((B,), np.float32)
        self.topk = np.full((B,), vocab, np.int32)

        self.sharing = engine._sharing
        if self.sharing is not None and self.sharing.schedule == "prefix":
            # prefix-aware admission: group same-prefix requests so
            # their residency windows overlap (sharing is an in-flight
            # property — a registered page lives only while a slot
            # holds it).  Group order is first arrival, so no group
            # starves; generations are bit-independent of the schedule
            # (sampling keys are (request, token-index)-addressed).
            groups: Dict[bytes, list] = {}
            for r in requests:
                groups.setdefault(r.keys.group, []).append(r)
            self.pending = collections.deque(
                r for grp in groups.values() for r in grp)
        else:
            self.pending = collections.deque(requests)
        #: preempted requests, FIFO: (_Slot, PagePayload, next token)
        self.suspended: collections.deque = collections.deque()
        # whole-prompt memo: keys.whole -> (first-token logits, state
        # snapshot).  Host-resident, per call, FIFO-capped; an
        # exact-duplicate prompt whose pages are all still registered
        # admits through PageTable.admit_cached with no prefill at all.
        self.memo: Dict[bytes, tuple] = {}
        # Prefilled admissions of one pass dispatch back to back and
        # their first tokens come to the host in one pull at the end of
        # the pass, so the host dispatches ahead of the device instead
        # of waiting on each request's token.  The pull runs inside the
        # last admission's `serve.admit` span, which stays open until
        # the pass ends or another kind of admission comes next.
        self.landing: List[tuple] = []     # (slot, _Slot, first token, span)
        self._admit_span: Optional[spans.span] = None
        self._freed = False

    # ------------------------------------------------------------ the loop
    def busy(self) -> bool:
        return (any(st is not None for st in self.slots)
                or bool(self.suspended) or bool(self.pending))

    def step(self) -> None:
        """One decode step over the live slots, then admissions into the
        slots it freed (admissions first when no slot is live)."""
        if all(st is None for st in self.slots):
            self.admit()
            if all(st is None for st in self.slots):
                if self.pending or self.suspended:   # pragma: no cover
                    raise RuntimeError(
                        "serve stalled: no slot admissible — resident-page "
                        "budget cannot hold any pending/suspended request")
                return             # every request ended on its first token
        engine, slots = self.engine, self.slots
        with spans.span("serve.step"):
            if self.table is not None:
                with spans.span("page_table.grow"):
                    self.grow()
                self.table.count_pages()
            active = [s for s in range(self.B) if slots[s] is not None]
            ctx = [int(self.pos[s]) + 1 for s in active]
            if engine._kernel_walk is not None and spans.recording():
                rows, blocks = engine._kernel_walk
                spans.count("paged_attention.blocks", blocks * len(ctx))
                spans.count("paged_attention.blocks_live",
                            sum(-(-c // rows) for c in ctx))
            with spans.span("serve.decode") as dec_span:
                logits, self.cache = engine.decode_step(
                    self.cache, self.tok, self.pos)
                keys = engine._keys(self.base, self.rid, self.emit)
                toks = self._sample(logits, keys, jnp.asarray(self.temp),
                                    jnp.asarray(self.topk))
            with spans.span("serve.token_pull") as pull_span:
                if engine.expert_rows is not None and spans.recording():
                    toks, rows = jax.device_get((toks, engine.expert_rows))
                    spans.count("moe.rows", int(rows.sum()))
                    spans.count("moe.rows_max",
                                int(rows.max(axis=-1).sum()))
                else:
                    toks = np.asarray(toks)
            spans.count("serve.decode_steps")
            self.hooks.record_decode(
                ctx, dec_span.seconds + pull_span.seconds)
            if self.trace is not None:
                # one trace step per decode step: every active slot's
                # resident pages (allocate-on-write: residency == the
                # context this step's KV sweep reads; the append lands
                # in the same set after grow()) plus whatever moved
                # between steps, with the weights re-streamed.
                for s in active:
                    self._note_pages(s)
                self.trace.record_step(self._pages, param_read=True)
                self._pages.clear()
            for s in active:
                st = slots[s]
                token = int(toks[s])
                st.out.append(token)
                st.emitted += 1
                st.pos += 1
                self.tok[s], self.pos[s], self.emit[s] = (token, st.pos,
                                                          st.emitted)
                if self._finished(st, token):
                    self.retire(s)
            self.admit()

    def finish(self) -> List[np.ndarray]:
        """The outputs, in input order."""
        if self.trace is not None and self._pages:
            # trailing page moves with no decode step after them (e.g. a
            # final admission that retired on its prefill token)
            self.trace.record_step(self._pages, param_read=False)
        return self.outputs  # type: ignore[return-value]

    # ----------------------------------------------------------- the slots
    def _occupy(self, s: int, st: _Slot, next_tok: int) -> None:
        self.slots[s] = st
        self.tok[s], self.pos[s] = next_tok, st.pos
        self.rid[s], self.emit[s] = st.req.req_id, st.emitted
        self.temp[s], self.topk[s] = st.req.temperature, st.req.top_k

    def _finished(self, st: _Slot, token: int) -> bool:
        if st.emitted >= st.req.max_new_tokens:
            return True
        if self.eos is not None and token == self.eos:
            return True
        return st.pos >= self.engine.max_ctx   # logical context exhausted

    def retire(self, s: int) -> None:
        st = self.slots[s]
        self.outputs[st.req.order] = np.asarray(st.out, np.int32)
        self.slots[s] = None
        if self.table is not None:
            with spans.span("page_table.release"):
                self.cache = self.table.release(self.cache, s)

    def suspend(self, victim: int) -> None:
        """Preempt a live slot: offload its pages to the host."""
        st = self.slots[victim]
        self._note_pages(victim)   # offload reads every held page
        self.cache, payload = self.table.offload(self.cache, victim, st.pos)
        self.suspended.append((st, payload, int(self.tok[victim])))
        self.slots[victim] = None
        self.hooks.record_page_out(st.pos)

    def grow(self) -> None:
        """Assign the pages this step's writes need; when a pool runs
        dry, preempt the NEWEST live request — including the grower
        itself, which then suspends and waits FIFO — so the oldest
        admitted request is only ever victimized by its own elders
        (FCFS progress is preserved).  Pages are shard-local, so only
        slots pinned to the grower's shard can free the pages it needs
        — victims come from that shard."""
        table, slots = self.table, self.slots
        cow: List[Tuple[int, int]] = []
        order = sorted((s for s in range(self.B) if slots[s] is not None),
                       key=lambda s: slots[s].req.order)
        for s in order:
            while slots[s] is not None:    # None: preempted by a grower
                self.cache, ok = table.prepare_step(
                    self.cache, s, int(self.pos[s]), cow_events=cow)
                if ok:
                    break
                g = table.shard_of(s)
                victims = [v for v in range(self.B) if slots[v] is not None
                           and table.shard_of(v) == g]
                victim = max(victims, key=lambda v: slots[v].req.order)
                if victim == s and len(victims) == 1:
                    raise RuntimeError(   # pragma: no cover
                        "paged cache: resident-page budget exhausted "
                        "with a single live slot in its shard — "
                        "unreachable when every per-shard extent "
                        "covers one full slot")
                self.suspend(victim)
        for _, layer_tokens in cow:
            self.hooks.record_cow(layer_tokens)

    def _note_pages(self, s: int) -> None:
        """Fold slot ``s``'s current page set into the next record."""
        if self.trace is not None:
            for si, pids in self.table.slot_page_ids(s):
                self._pages.setdefault(si, set()).update(pids)

    # ----------------------------------------------------------- admission
    def admit(self) -> None:
        """Fill free slots: restore suspended requests first, then admit
        pending ones, in passes until a pass frees no slot."""
        try:
            while True:
                self._freed = False
                self._admit_pass()
                self._land()
                if not (self._freed and (self.pending or self.suspended)):
                    break
        finally:
            self._close_admit()

    def _admit_pass(self) -> None:
        table = self.table
        for s in range(self.B):
            while self.slots[s] is None and (self.pending or self.suspended):
                if self.suspended:
                    self._land()
                    # resume FIFO before admitting new work; if the
                    # oldest suspension cannot fit yet, wait for pages
                    # (live slots will retire) rather than admitting
                    # page-hungry new requests around it.
                    st, payload, next_tok = self.suspended[0]
                    if not table.can_restore(payload, s):
                        break
                    self.suspended.popleft()
                    self.cache = table.restore(self.cache, s, payload)
                    self._note_pages(s)   # restore writes the new pages
                    self._occupy(s, st, next_tok)
                    self.hooks.record_page_in(payload.tokens)
                    continue
                req = self.pending[0]
                plen = req.prompt.shape[0]
                if (self.sharing is not None and req.keys.whole in self.memo
                        and table.can_admit_cached(s, plen, req.keys)):
                    self._admit_cached(s)
                    continue
                if table is not None and not table.can_admit(plen, s,
                                                             req.keys):
                    break                  # wait for pages to free
                self._admit_prefill(s)

    def _admit_cached(self, s: int) -> None:
        """Full skip: the exact prompt prefilled earlier and every page
        is still registered — attach it all, restore recurrent state
        from the host snapshot, and replay the memoized first-token
        logits (bit-identical: both round trips are exact).  No prefill
        program runs."""
        self._land()
        req = self.pending.popleft()
        self._queue_wait(req, time.perf_counter_ns())
        plen = req.prompt.shape[0]
        logits, snapshot = self.memo[req.keys.whole]
        self.cache = self.table.admit_cached(self.cache, s, plen, req.keys,
                                             snapshot)
        self._note_pages(s)
        self._record_shared(plen, skipped_prefill=True)
        first = int(np.asarray(self._first_token(req, jnp.asarray(logits)))[0])
        st = _Slot(req, pos=plen, first_token=first)
        self._occupy(s, st, first)
        if self._finished(st, first):
            self.retire(s)

    def _admit_prefill(self, s: int) -> None:
        """Prefill the next pending request into slot ``s``; its first
        token lands with the pass's others (:meth:`_land`)."""
        req = self.pending.popleft()
        plen = req.prompt.shape[0]
        self._close_admit()
        adm_span = spans.span("serve.admit", request=req.req_id)
        self._admit_span = adm_span.__enter__()
        logits, self.cache, one = self.engine.prefill_into(
            self.cache, s, req.prompt, req.keys)
        sharing = self.sharing
        if sharing is not None:
            self._record_shared(plen)
            if (sharing.memo_size > 0 and self.table.fully_shareable(plen)
                    and req.keys.whole not in self.memo):
                self.memo[req.keys.whole] = (
                    np.asarray(logits), self.table.state_snapshot(one))
                while len(self.memo) > sharing.memo_size:
                    self.memo.pop(next(iter(self.memo)))
        self._note_pages(s)   # admission scatters the prefill
        with spans.span("serve.first_token"):
            first = self._first_token(req, logits)
        self._queue_wait(req, adm_span.start_ns)
        st = _Slot(req, pos=plen, first_token=0)
        self._occupy(s, st, 0)            # its token lands later
        self.landing.append((s, st, first, adm_span))

    def _land(self) -> None:
        """Pull the landing admissions' first tokens and hand each to
        its slot and the telemetry, in admission order."""
        if not self.landing:
            return
        try:
            with spans.span("serve.first_token"):
                firsts = jax.device_get([a[2] for a in self.landing])
        finally:
            self._close_admit()
        buckets = self.engine.buckets
        for (s, st, _, adm_span), first in zip(self.landing, firsts):
            first = int(first[0])
            st.out[0] = self.tok[s] = first
            plen = st.req.prompt.shape[0]
            self.hooks.record_prefill(plen, adm_span.seconds,
                                      padded_len=buckets.bucket_for(plen))
            if self._finished(st, first):
                self.retire(s)             # this slot admits again
                self._freed = True
        self.landing.clear()

    def _close_admit(self) -> None:
        if self._admit_span is not None:
            self._admit_span.__exit__(None, None, None)
            self._admit_span = None

    # ------------------------------------------------------------- helpers
    def _queue_wait(self, req, end_ns: int) -> None:
        """Stamp ``req``'s wait, from the call's entry to ``end_ns``."""
        spans.add("serve.queue_wait", self.t_entry, end_ns, "serve.call",
                  req.req_id)

    def _first_token(self, req, logits):
        """``req``'s first token sampled from its ``[1, vocab]`` logits
        with key (request, 0), on the device."""
        key = self.engine._keys(self.base, np.asarray([req.req_id], np.int32),
                                np.zeros((1,), np.int32))
        return self._sample(logits, key,
                            np.asarray([req.temperature], np.float32),
                            np.asarray([req.top_k], np.int32))

    def _sample(self, logits, keys, temps, topks):
        return self.engine._sample(logits, keys, temps, topks,
                                   self.use_top_k)

    def _record_shared(self, plen: int, **kw) -> None:
        adm = self.table.last_admit
        self.hooks.record_admit_shared(plen, adm["attached_layer_tokens"],
                                       adm["total_layer_tokens"], **kw)
