"""The serve loop's spans and counters (``repro.serve.spans``): recorded
only while a profiler trace is collected, one clock for the spans and
the telemetry hooks, page counts that match the contexts served, the
in-call compile count, and the spans in the written trace."""
import collections
import glob
import math
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import TransformerLM
from repro.serve import ServeEngine, spans
from repro.serve.paging import PagedCacheConfig

PAGE = 4
IDS = [40, 41, 42, 43, 44]


class Hooks:
    """Telemetry sink that keeps what each hook was handed."""

    def __init__(self):
        self.prefill, self.decode = [], []

    def record_prefill(self, plen, dt=0.0, padded_len=None):
        self.prefill.append(dt)

    def record_decode(self, ctx_lengths, dt=0.0):
        self.decode.append((list(ctx_lengths), dt))


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = TransformerLM(cfg)
    return ServeEngine(model, model.init(jax.random.key(0)), max_len=32,
                       max_batch=3, paged=PagedCacheConfig(page_size=PAGE))


@pytest.fixture(scope="module")
def prompts(engine):
    rng = np.random.default_rng(0)
    vocab = engine.model.cfg.vocab_size
    return [rng.integers(0, vocab, (n,)).astype(np.int32)
            for n in (5, 9, 3, 12, 7)]


def traced_serve(engine, prompts, where, **kw):
    """One ``serve`` under a profiler trace written to ``where``: the
    record, the telemetry hooks and the outputs."""
    hooks = Hooks()
    spans.clear()
    jax.profiler.start_trace(str(where))
    try:
        out = engine.serve(prompts, 6, telemetry=hooks, **kw)
    finally:
        jax.profiler.stop_trace()
    rec = spans.recorded()
    spans.clear()
    return rec, hooks, out


@pytest.fixture(scope="module")
def traced(engine, prompts, tmp_path_factory):
    engine.serve(prompts, 6)                # compile every program first
    where = tmp_path_factory.mktemp("trace")
    rec, hooks, out = traced_serve(engine, prompts, where, request_ids=IDS)
    return rec, hooks, out, where


def by_name(rec, name):
    return [s for s in rec.spans if s.name == name]


def test_nothing_recorded_without_a_trace(engine, prompts):
    spans.clear()
    assert not spans.recording()
    engine.serve(prompts, 6)
    rec = spans.recorded()
    assert rec.spans == [] and rec.counts == {}


def test_tracing_leaves_outputs_unchanged(engine, prompts, traced):
    out = traced[2]
    for a, b in zip(engine.serve(prompts, 6, request_ids=IDS), out):
        np.testing.assert_array_equal(a, b)


def test_one_queue_wait_per_request(traced):
    rec = traced[0]
    waits = by_name(rec, "serve.queue_wait")
    assert sorted(s.request for s in waits) == IDS
    call, = by_name(rec, "serve.call")
    admits = {s.request: s for s in by_name(rec, "serve.admit")}
    for w in waits:
        assert w.parent == "serve.call"
        assert w.start_ns == call.start_ns
        assert w.end_ns == admits[w.request].start_ns


def test_one_decode_span_per_record_decode(traced):
    rec, hooks = traced[0], traced[1]
    n = len(hooks.decode)
    assert n > 0
    for name in ("serve.step", "serve.decode", "serve.token_pull",
                 "page_table.grow"):
        assert len(by_name(rec, name)) == n, name
    assert rec.counts["serve.decode_steps"] == n


def test_span_parents(traced):
    rec = traced[0]
    parents = collections.defaultdict(set)
    for s in rec.spans:
        parents[s.name].add(s.parent)
    assert parents["serve.call"] == {None}
    assert parents["serve.step"] == {"serve.call"}
    assert parents["serve.admit"] <= {"serve.call", "serve.step"}
    for child in ("serve.prefill", "page_table.insert", "serve.first_token"):
        assert parents[child] == {"serve.admit"}, child
    for child in ("serve.decode", "serve.token_pull", "page_table.grow"):
        assert parents[child] == {"serve.step"}, child
    assert parents["page_table.release"] <= {"serve.call", "serve.step"}
    assert len(by_name(rec, "page_table.release")) == len(IDS)


def test_hook_dt_is_the_span_duration(traced):
    rec, hooks = traced[0], traced[1]
    admits = sorted(by_name(rec, "serve.admit"), key=lambda s: s.start_ns)
    assert hooks.prefill == [s.seconds for s in admits]
    pairs = zip(by_name(rec, "serve.decode"),
                by_name(rec, "serve.token_pull"))
    assert [dt for _, dt in hooks.decode] == [
        d.seconds + p.seconds for d, p in pairs]


def test_pages_live_match_the_contexts(engine, prompts, traced):
    rec, hooks = traced[0], traced[1]
    live = sum(math.ceil(c / PAGE) for ctx, _ in hooks.decode for c in ctx)
    assert rec.counts["page_table.pages_live"] == live
    assert rec.counts["page_table.pages_pool"] == (
        len(hooks.decode) * engine.page_table.resident_pages)
    # admission takes a prompt's pages; each later page is one
    # assignment, at the first step that writes into it (the last of a
    # request's 5 decode steps runs at context plen + 5)
    assert rec.counts["page_table.assigns"] == sum(
        math.ceil((len(p) + 5) / PAGE) - math.ceil(len(p) / PAGE)
        for p in prompts)
    assert "page_table.forks" not in rec.counts


def test_every_assignment_rides_the_decode_step(traced):
    """With no preemption each page assignment is applied by the decode
    step it was made for: the folded count is the assignment count."""
    rec = traced[0]
    assert rec.counts["page_table.assigns_folded"] == \
        rec.counts["page_table.assigns"] > 0


def test_kernel_blocks_match_the_contexts(engine, prompts, traced,
                                         tmp_path, monkeypatch):
    """On the paged kernel's path, two-page blocks (``BLOCK_BYTES`` cut to
    two of this model's pages): every live slot counts the 4 blocks of
    its 8-page table, and the blocks that hold its context; the tokens
    are the gather engine's."""
    from repro.kernels.paged_attention import kernel as paged_kernel
    cfg = engine.model.cfg
    page_bytes = PAGE * cfg.n_kv_heads * cfg.resolved_head_dim * 4
    monkeypatch.setattr(paged_kernel, "BLOCK_BYTES", 2 * page_bytes)
    jax.clear_caches()          # no kernel traced at another block size
    try:
        kern = ServeEngine(engine.model, engine.params, max_len=32,
                           max_batch=3, paged=PagedCacheConfig(page_size=PAGE),
                           decode_backend="pallas_paged")
        kern.serve(prompts, 6)
        rec, hooks, out = traced_serve(kern, prompts, tmp_path,
                                       request_ids=IDS)
    finally:
        jax.clear_caches()
    assert kern._kernel_walk == (2 * PAGE, 4)
    ctxs = [c for ctx, _ in hooks.decode for c in ctx]
    assert rec.counts["paged_attention.blocks"] == 4 * len(ctxs)
    assert rec.counts["paged_attention.blocks_live"] == sum(
        math.ceil(c / (2 * PAGE)) for c in ctxs)
    for a, b in zip(traced[2], out):
        np.testing.assert_array_equal(a, b)
    assert "paged_attention.blocks" not in traced[0].counts


def test_compiles_counted_inside_serve(engine, prompts, tmp_path):
    engine.serve(prompts, 6)
    rec, _, _ = traced_serve(engine, prompts, tmp_path / "warm")
    assert rec.counts.get("serve.compiles", 0) == 0
    longer = [np.arange(20, dtype=np.int32) % 7]    # the 32-token bucket
    rec, _, _ = traced_serve(engine, longer, tmp_path / "cold")
    assert rec.counts["serve.compiles"] > 0


def test_spans_in_the_written_trace(traced):
    where = traced[3]
    path, = glob.glob(os.path.join(str(where), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = collections.Counter(
        ev.name for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events)
    rec = traced[0]
    for name in ("serve.call", "serve.admit", "serve.prefill",
                 "page_table.insert", "serve.first_token", "serve.step",
                 "page_table.grow", "serve.decode", "serve.token_pull",
                 "page_table.release"):
        assert names[name] == len(by_name(rec, name)), name
    assert names["serve.queue_wait"] == 0       # stamps only
