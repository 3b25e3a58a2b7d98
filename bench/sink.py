"""Timestamp-only telemetry sink and the window's arithmetic.

``ServeEngine.serve(telemetry=sink)`` calls ``record_prefill`` once a
request's first token is on the host, and ``record_decode`` once the
tokens of a decode step over every live slot are.  The sink stamps each
call with the host clock and does nothing else in the loop.

It also follows the engine's schedule, which is fixed while prefix
sharing is off and the pool holds every slot: admission is first come,
first served, into the lowest free slot, and a slot retires once its
request has its ``new_tokens``.  So the ``k``-th prefill of a job is its
``k``-th request, and each decode step's live contexts must equal the
schedule's; a mismatch raises rather than measure the wrong thing.

From the stamps, for the window ``[start, deadline]``:

* time to first token: from the job's submission to the request's first
  token; a request still waiting at the deadline counts its wait so far;
* gaps between tokens: a continuing slot's gap is the time between two
  decode steps, a newly admitted slot's first gap runs from its own
  prefill;
* tokens: one per prefill and one per live slot per decode step, each
  counted only when its stamp is inside the window.

The first call after the deadline raises :class:`WindowClosed`, which
ends the job in flight.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence

__all__ = ["WindowClosed", "Sink", "WindowStats"]


class WindowClosed(Exception):
    """Raised from a sink hook once the window's deadline has passed."""


@dataclasses.dataclass
class WindowStats:
    seconds: float
    tokens: int
    ttft_s: List[float]
    gaps_s: List[float]
    prefill_s: float              # engine-timed prefill seconds in window
    prefill_tokens: List[int]     # prompt lengths prefilled in window
    decode_s: float               # engine-timed decode seconds in window
    decode_ctx: List[List[int]]   # live contexts of each decode in window
    traced_decode_ctx: List[List[int]]   # every decode the run made
    requests: int                 # requests submitted in the window
    slots: int


class Sink:
    def __init__(self, slots: int, start: float, deadline: float,
                 clock=time.perf_counter):
        self.slots = int(slots)
        self.start, self.deadline = float(start), float(deadline)
        self.clock = clock
        self._job = None
        self.ttft: List[float] = []
        self.gaps: List[float] = []
        self.tokens = 0
        self.prefill_s = 0.0
        self.prefill_tokens: List[int] = []
        self.decode_s = 0.0
        self.decode_ctx: List[List[int]] = []
        self.all_decode_ctx: List[List[int]] = []
        self.requests = 0

    # ------------------------------------------------------------ jobs
    def begin_job(self, plens: Sequence[int], new_tokens: int,
                  submitted: Optional[float] = None) -> None:
        self._close_job()
        self._job = dict(plens=list(plens), new=int(new_tokens),
                         t0=self.clock() if submitted is None else submitted,
                         next=0)
        self._slot = [None] * self.slots   # slot -> [req, emitted, last t]
        self.requests += len(plens)

    def _close_job(self, at: Optional[float] = None) -> None:
        """Requests of the job in flight that never got a first token
        enter the TTFT tail with their wait so far."""
        job = self._job
        if job is None:
            return
        end = self.deadline if at is None else at
        for _ in range(job["next"], len(job["plens"])):
            self.ttft.append(end - job["t0"])
        self._job = None

    def close(self) -> WindowStats:
        self._close_job(self.deadline)
        return WindowStats(
            seconds=self.deadline - self.start, tokens=self.tokens,
            ttft_s=self.ttft, gaps_s=self.gaps, prefill_s=self.prefill_s,
            prefill_tokens=self.prefill_tokens, decode_s=self.decode_s,
            decode_ctx=self.decode_ctx,
            traced_decode_ctx=self.all_decode_ctx,
            requests=self.requests, slots=self.slots)

    def job_done(self) -> None:
        """The job in flight returned: every request got its tokens."""
        job = self._job
        if job is not None and job["next"] != len(job["plens"]):
            raise RuntimeError(
                f"job ended after {job['next']} of {len(job['plens'])} "
                f"prefills: the schedule is not the one the sink follows")
        self._job = None

    # ------------------------------------------------------ engine hooks
    def configure_decode(self, backend: str, paged: bool) -> None:
        pass

    def record_prefill(self, plen: int, dt: float = 0.0,
                       padded_len: Optional[int] = None) -> None:
        t = self.clock()
        job = self._job
        k = job["next"]
        if k >= len(job["plens"]) or job["plens"][k] != int(plen):
            raise RuntimeError(
                f"prefill of a {plen}-token prompt where the schedule has "
                f"request {k} of the job next")
        if t > self.deadline:
            raise WindowClosed
        job["next"] = k + 1
        self.ttft.append(t - job["t0"])
        self.tokens += 1
        self.prefill_s += float(dt)
        self.prefill_tokens.append(int(plen))
        slot = self._slot.index(None)
        if job["new"] > 1:
            self._slot[slot] = [k, 1, t]

    def record_decode(self, ctx_lengths: Sequence[int], dt: float = 0.0
                      ) -> None:
        t = self.clock()
        job = self._job
        live = [s for s in range(self.slots) if self._slot[s] is not None]
        want = [job["plens"][self._slot[s][0]] + self._slot[s][1]
                for s in live]
        ctx = [int(c) for c in ctx_lengths]
        if ctx != want:
            raise RuntimeError(
                f"decode over contexts {ctx[:8]}... where the schedule has "
                f"{want[:8]}...")
        self.all_decode_ctx.append(ctx)
        if t > self.deadline:
            raise WindowClosed
        self.tokens += len(ctx)
        self.decode_s += float(dt)
        self.decode_ctx.append(ctx)
        for s in live:
            st = self._slot[s]
            self.gaps.append(t - st[2])
            st[1] += 1
            st[2] = t
            if st[1] >= job["new"]:
                self._slot[s] = None

    def record_page_out(self, ctx: int) -> None:
        raise RuntimeError("a slot was offloaded: the pool is sized so "
                           "that this never happens in the benchmark")

    record_page_in = record_page_out
