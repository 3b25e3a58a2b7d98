"""Spans and counters of the serve loop, on the profiler's clock.

While a profiler trace is being collected (``jax.profiler.start_trace``
to ``stop_trace``, or ``jax.profiler.trace``), and only then, each
:class:`span` is a ``jax.profiler.TraceAnnotation`` in the trace's host
plane, on the device operations' clock, and spans and :func:`count`
calls are kept in memory: :func:`recorded` returns them, :func:`clear`
empties them.  Recording has no switch of its own.  Like the profiler,
the record belongs to the process.  A span's parent is the innermost
span open around it, unless given.  The serve loop records (parent in
brackets):

* ``serve.call``: one :meth:`ServeEngine.serve` call;
* ``serve.queue_wait`` (``serve.call``): from the call's entry to the
  start of one request's admission, with the request's id;
* ``serve.admit`` (``serve.call`` or ``serve.step``): one prefilled
  admission, with the request's id: ``serve.prefill`` (the program),
  ``page_table.insert`` (into the pages or the contiguous cache) and
  ``serve.first_token`` (key and sample); the last admission of a pass
  holds a second ``serve.first_token``, the pull of every first token
  of the pass to the host;
* ``serve.step`` (``serve.call``): one decode loop iteration:
  ``page_table.grow`` (the step's page assignments, picked on the host:
  the decode step applies them), ``serve.decode``
  (decode, key and sample dispatches), ``serve.token_pull`` (the wait
  for the tokens), ``page_table.release`` (per retiring slot), admits.

Counters: ``serve.decode_steps``; ``page_table.pages_live`` and
``page_table.pages_pool``, the KV pages live slots hold and the pool's
KV pages, summed over decode steps; ``page_table.assigns`` and
``page_table.forks``, pages ``prepare_step`` assigned or forked;
``page_table.assigns_folded``, the assignments a decode step applied
(the others went through the page table's flush program);
``serve.compiles``, programs compiled while ``serve.call`` was open;
for a model with experts, ``moe.rows``, the rows its decode steps
routed, summed over steps, layers and experts, and ``moe.rows_max``,
the busiest expert's rows in each layer and step, summed (read from the
step's rows per expert, pulled with the tokens); on the paged kernel's
path, ``paged_attention.blocks``, the live slots times the kernel's
blocks a slot, and ``paged_attention.blocks_live``, the blocks of
those slots that hold a row of their context, summed over decode steps
(one layer's walk over a table of the whole context, counted on the
host from the slots' positions).
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, NamedTuple, Optional

import jax

__all__ = ["Span", "Record", "span", "add", "count", "recording",
           "recorded", "clear", "named"]

_Annotation = jax.profiler.TraceAnnotation
_is_enabled = _Annotation.is_enabled      # jaxlib TraceMe's own flag
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start_ns: int             # time.perf_counter_ns()
    end_ns: int
    parent: Optional[str]
    request: Optional[int]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class Record(NamedTuple):
    spans: List[Span]
    counts: Dict[str, int]


_spans: List[Span] = []
_counts: Dict[str, int] = {}
#: names of the open spans, outermost first (one serving thread)
_open: List[str] = []


def recording() -> bool:
    """Whether a profiler trace is being collected (``TraceMe``'s flag)."""
    return _is_enabled()


class span:
    """``with span(name) as s:`` times the block; afterwards ``s.seconds``
    is its duration, from the stamps the record keeps.  A
    ``TraceAnnotation`` made with no trace running records nothing, so
    a span makes one only when the flag is up."""

    __slots__ = ("name", "parent", "request", "start_ns", "end_ns",
                 "_annotation")

    def __init__(self, name: str, parent: Optional[str] = None,
                 request: Optional[int] = None):
        self.name, self.parent, self.request = name, parent, request

    def __enter__(self) -> "span":
        if _is_enabled():
            self._annotation = _Annotation(self.name)
            self._annotation.__enter__()
        else:
            self._annotation = None
        if self.parent is None and _open:
            self.parent = _open[-1]
        _open.append(self.name)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        _open.pop()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            _spans.append(Span(self.name, self.start_ns, self.end_ns,
                               self.parent, self.request))

    seconds = Span.seconds


def add(name: str, start_ns: int, end_ns: int,
        parent: Optional[str] = None, request: Optional[int] = None) -> None:
    """Record a span from stamps taken elsewhere (it has no annotation)."""
    if _is_enabled():
        _spans.append(Span(name, start_ns, end_ns, parent, request))


def count(name: str, n: int = 1) -> None:
    if _is_enabled():
        _counts[name] = _counts.get(name, 0) + n


def recorded() -> Record:
    """Copies of the spans and counters recorded since :func:`clear`."""
    return Record(list(_spans), dict(_counts))


def clear() -> None:
    _spans.clear()
    _counts.clear()


def named(name: str, fn: Callable) -> Callable:
    """``fn`` under ``name``: ``jax.jit`` names the program it compiles
    ``jit_<name>``, which a trace's XLA Modules line shows."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)
    call.__name__ = call.__qualname__ = name
    return call


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT and "serve.call" in _open:
        count("serve.compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
