"""Host cost of the serve loop's spans with no profiler trace running.

One decode step of ``ServeEngine.serve`` opens four spans
(``serve.step`` around ``page_table.grow``, ``serve.decode`` and
``serve.token_pull``), asks the page table to count its pages and bumps
one counter.  This times that sequence with nothing inside it, so the
number is what the instrumentation adds to each step on this host:

    PYTHONPATH=src python benchmarks/span_cost.py

Prints microseconds per step: the median and range of seven repeats of
100,000 steps.
"""
from __future__ import annotations

import statistics
import timeit

from repro.serve import spans


def step() -> float:
    with spans.span("serve.step"):
        with spans.span("page_table.grow"):
            pass
        spans.recording()                  # PageTable.count_pages's check
        with spans.span("serve.decode") as dec:
            pass
        with spans.span("serve.token_pull") as pull:
            pass
        spans.count("serve.decode_steps")
    return dec.seconds + pull.seconds


def main() -> None:
    if spans.recording():
        raise SystemExit("a profiler trace is running: this times the "
                         "spans with none")
    n = 100_000
    us = sorted(1e6 * timeit.timeit(step, number=n) / n for _ in range(7))
    print(f"spans per decode step, no trace: median "
          f"{statistics.median(us):.2f} us, range {us[0]:.2f}-{us[-1]:.2f} "
          f"us")


if __name__ == "__main__":
    main()
