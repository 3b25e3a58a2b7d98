"""The metrics that read the program's own span record, fed a record made
by a tiny traced serve and checked against counts made here by hand; an
empty record, or a program that keeps none, reads nothing."""
import math
import sys

import jax
import numpy as np
import pytest

from bench.spec import BENCH_DIR, Bench

PAGE = 4
NEW = 6
METRICS = ("engine.queue_wait_p90_s", "step.host_ms",
           "page_table.ms_per_step", "page_table.live_share",
           "engine.compiles")


class Hooks:
    def __init__(self):
        self.prefill, self.decode = [], []

    def record_prefill(self, plen, dt=0.0, padded_len=None):
        self.prefill.append(dt)

    def record_decode(self, ctx_lengths, dt=0.0):
        self.decode.append(list(ctx_lengths))


@pytest.fixture(scope="module")
def bench():
    return Bench(BENCH_DIR.parent)


@pytest.fixture(scope="module")
def engine():
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM
    from repro.serve import ServeEngine
    from repro.serve.paging import PagedCacheConfig
    model = TransformerLM(get_config("qwen1.5-0.5b", smoke=True))
    return ServeEngine(model, model.init(jax.random.key(0)), max_len=32,
                       max_batch=3, paged=PagedCacheConfig(page_size=PAGE))


def prompts_of(lengths):
    rng = np.random.default_rng(1)
    return [rng.integers(0, 256, (n,)).astype(np.int32) for n in lengths]


def traced(engine, prompts, where):
    """The record and hooks of one traced ``serve``, and the backend
    compilations JAX reported meanwhile."""
    from repro.serve import spans
    hooks, compiled = Hooks(), []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)
    jax.monitoring.register_event_duration_secs_listener(listen)
    spans.clear()
    jax.profiler.start_trace(str(where))
    try:
        engine.serve(prompts, NEW, telemetry=hooks)
    finally:
        jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(listen)
    return spans.recorded(), hooks, len(compiled)


@pytest.fixture
def record(engine, tmp_path):
    prompts = prompts_of((5, 9, 3, 12, 7))
    engine.serve(prompts, NEW)
    rec, hooks, _ = traced(engine, prompts, tmp_path)
    yield rec, hooks
    from repro.serve import spans
    spans.clear()


def of(rec, name):
    return [s for s in rec.spans if s.name == name]


def read(bench, name):
    return bench.metric(name).read(None)


def test_queue_wait_p90(bench, record):
    rec, _ = record
    waits = sorted(s.end_ns - s.start_ns for s in of(rec, "serve.queue_wait"))
    assert len(waits) == 5
    # numpy's p90 of 5 values: 0.6 of the way from the 4th to the 5th
    want = 1e-9 * (waits[3] + 0.6 * (waits[4] - waits[3]))
    assert read(bench, "engine.queue_wait_p90_s") == pytest.approx(want)


def test_step_host_ms(bench, record):
    rec, hooks = record
    own = []
    for st in of(rec, "serve.step"):
        inner = [s for s in rec.spans
                 if s.name in ("serve.decode", "serve.token_pull",
                               "serve.admit")
                 and st.start_ns <= s.start_ns and s.end_ns <= st.end_ns]
        own.append(st.end_ns - st.start_ns
                   - sum(s.end_ns - s.start_ns for s in inner))
    assert len(own) == len(hooks.decode)
    assert read(bench, "step.host_ms") == pytest.approx(
        1e-6 * sum(own) / len(own))
    assert 0 < read(bench, "step.host_ms")


def test_page_table_ms_per_step(bench, record):
    rec, hooks = record
    names = ("page_table.grow", "page_table.release", "page_table.insert")
    ns = sum(s.end_ns - s.start_ns for s in rec.spans if s.name in names)
    assert len(of(rec, "page_table.insert")) == 5
    assert read(bench, "page_table.ms_per_step") == pytest.approx(
        1e-6 * ns / len(hooks.decode))


def test_page_table_live_share(bench, engine, record):
    _, hooks = record
    live = sum(math.ceil(c / PAGE) for ctx in hooks.decode for c in ctx)
    pool = len(hooks.decode) * engine.page_table.resident_pages
    assert read(bench, "page_table.live_share") == pytest.approx(
        100.0 * live / pool)


def test_engine_compiles(bench, engine, tmp_path):
    from repro.serve import spans
    prompts = prompts_of((5, 9))
    engine.serve(prompts, NEW)
    rec, _, n = traced(engine, prompts, tmp_path / "warm")
    assert n == 0 and read(bench, "engine.compiles") == 0
    rec, _, n = traced(engine, prompts_of((20,)), tmp_path / "cold")
    assert n > 0 and read(bench, "engine.compiles") == n
    spans.clear()


@pytest.mark.parametrize("name", METRICS)
def test_empty_record_reads_nothing(bench, name):
    from repro.serve import spans
    spans.clear()
    assert read(bench, name) is None


@pytest.mark.parametrize("name", METRICS)
def test_program_without_a_record_reads_nothing(bench, name, monkeypatch):
    import repro.serve
    monkeypatch.delattr(repro.serve, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    assert read(bench, name) is None
