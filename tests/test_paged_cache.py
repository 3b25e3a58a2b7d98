"""Paged==contiguous equivalence suite for the block-table cache.

The contract under test: serving through the paged cache
(:class:`repro.serve.paging.PageTable` + ``PagedKVCache`` /
``PagedSSMCache`` / ``PagedRGLRUCache``) is *bit-identical* to serving
through the contiguous per-slot cache — prefill logits, every resident
cache page (the ``logical_view`` gather must reproduce the contiguous
buffers exactly), each decode step's logits, and the full generation
continuation.  This is what lets the engine grow a slot's page list
past the old contiguous ``max_len``, and offload cold pages to host
under a resident-page budget, without perturbing a single token.

Exercised per family: global append caches, local ring caches
(including page sizes that do not divide the ring length — partial
pages), Mamba/RG-LRU state pages and conv tails, and dropless-MoE
decode — i.e. all 10 ``repro.configs`` entries.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, strategies as st

from repro.configs import ARCH_IDS, get_config
from repro.models.transformer import TransformerLM
from repro.serve import (PagedCacheConfig, PageTable, ServeEngine,
                         ServeTelemetry, TrafficModel, logical_view)
from repro.serve.paging import NO_PAGE

MAX_CTX = 24     # logical context capacity (and contiguous cache length)
BUCKET = 16      # padded prefill shape (one executable per arch)
MAX_PLEN = 12    # property-test prompt lengths: 1..MAX_PLEN
PAGE = 5         # deliberately not a divisor of MAX_CTX or any window

_CACHED = {}


def _arch(arch, page_size=PAGE):
    """(model, params, jitted padded prefill, jitted decode, jitted
    contiguous insert, PageTable) — cached per (arch, page_size)."""
    key = (arch, page_size)
    if key not in _CACHED:
        cfg = get_config(arch, smoke=True)
        model = TransformerLM(cfg)
        params = model.init(jax.random.key(0))
        prefill = jax.jit(
            lambda p, t, n: model.prefill(p, t, MAX_CTX, lengths=n))
        table = PageTable(model, max_batch=2, max_ctx=MAX_CTX,
                          page_size=page_size)
        _CACHED[key] = (model, params, prefill, jax.jit(model.decode_step),
                        jax.jit(ServeEngine._insert_cache), table)
    return _CACHED[key]


def _prefill_slot(model, params, prefill, row):
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :row.shape[0]] = row
    return prefill(params, jnp.asarray(padded),
                   jnp.asarray([row.shape[0]], jnp.int32))


def _assert_views_equal(cache_c, cache_p, msg):
    """Every resident page, gathered back to the contiguous layout,
    must equal the contiguous cache bit-for-bit (including the zero
    rows of never-written positions)."""
    view = logical_view(cache_p)
    leaves_c = jax.tree_util.tree_flatten_with_path(cache_c)[0]
    leaves_p = jax.tree_util.tree_leaves(view)
    assert len(leaves_c) == len(leaves_p)
    for (path, a), b in zip(leaves_c, leaves_p):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{msg}: cache leaf {jax.tree_util.keystr(path)}")


def _build_pair(arch, plens, page_size=PAGE):
    """Admit ``plens`` prompts into slot 0/1 of both cache forms."""
    model, params, prefill, decode, insert, table = _arch(arch, page_size)
    cfg = model.cfg
    cache_c = model.init_cache(2, MAX_CTX)
    table.reset()
    cache_p = table.init_cache()
    toks = []
    for s, pl in enumerate(plens):
        row = np.random.default_rng(100 * pl + s).integers(
            0, cfg.vocab_size, (pl,)).astype(np.int32)
        logits, one = _prefill_slot(model, params, prefill, row)
        cache_c = insert(cache_c, one, jnp.asarray(s, jnp.int32))
        cache_p = table.admit(cache_p, one, s, pl)
        toks.append(int(jnp.argmax(logits[0])))
    return (model, params, decode, table, cache_c, cache_p,
            np.asarray(toks, np.int32), np.asarray(plens, np.int32))


_FOLDED = {}


def _decode_folded(table, params, cache, tok, pos):
    """The paged side's decode, as the engine's step runs it: one
    program that applies the table's pending assignments
    (``PageTable.apply_assignments``, handed over by ``fold_pending``)
    and then runs the model's step."""
    k = len(table.kv_streams)
    if table not in _FOLDED:
        _FOLDED[table] = jax.jit(
            lambda p, c, t, q, rows: table.model.decode_step(
                p, table.apply_assignments(c, rows[:k], rows[k:]), t, q))
    rows = np.empty((2 * k, table.max_batch), np.int32)
    table.fold_pending(rows)
    return _FOLDED[table](params, cache, tok, pos, rows)


def _lockstep(model, params, decode, table, cache_c, cache_p,
              tok, pos, steps, msg):
    """Decode both cache forms in lockstep, asserting bitwise equality
    of per-step logits and of every resident page after each step.  The
    paged side's step applies the page assignments ``prepare_step``
    left pending."""
    tok_c = tok_p = jnp.asarray(tok)
    for i in range(steps):
        for s in range(pos.shape[0]):
            cache_p, ok = table.prepare_step(cache_p, s, int(pos[s]))
            assert ok, f"{msg}: pool exhausted at step {i}"
        posj = jnp.asarray(pos)
        lc, cache_c = decode(params, cache_c, tok_c, posj)
        lp, cache_p = _decode_folded(table, params, cache_p, tok_p, posj)
        np.testing.assert_array_equal(
            np.asarray(lc), np.asarray(lp),
            err_msg=f"{msg}: decode step {i} logits")
        _assert_views_equal(cache_c, cache_p, f"{msg}: after step {i}")
        tok_c = jnp.argmax(lc, -1).astype(jnp.int32)
        tok_p = jnp.argmax(lp, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok_c), np.asarray(tok_p),
                                      err_msg=f"{msg}: step {i} tokens")
        pos = pos + 1
    return cache_c, cache_p, tok_c, pos


def _check_arch(arch, plen):
    plens = (plen, (plen + 5) % MAX_PLEN + 1)   # mixed per-slot lengths
    (model, params, decode, table, cache_c, cache_p,
     tok, pos) = _build_pair(arch, plens)
    _assert_views_equal(cache_c, cache_p,
                        f"{arch} plens={plens}: after insert")
    # decode past BUCKET so growth allocates pages mid-flight
    steps = min(6, MAX_CTX - max(plens))
    _lockstep(model, params, decode, table, cache_c, cache_p, tok,
              pos, steps, f"{arch} plens={plens}")


@given(plen=st.integers(1, MAX_PLEN))
@settings(max_examples=4, deadline=None)
def test_paged_decode_bit_identical_all_archs(plen):
    """Property: for every configured arch, block-table paged decode is
    bit-identical to contiguous decode — prefill hand-off, every
    resident cache page, per-step logits, and the greedy continuation."""
    for arch in ARCH_IDS:
        _check_arch(arch, plen)


@pytest.mark.parametrize("page_size", [1, 3, 8, MAX_CTX])
def test_page_size_extremes(page_size):
    """Row-granular (1), partial-page (3), divisor (8) and whole-cache
    (MAX_CTX) page sizes all reproduce contiguous decode."""
    (model, params, decode, table, cache_c, cache_p,
     tok, pos) = _build_pair("qwen1.5-0.5b", (5, 9), page_size)
    _lockstep(model, params, decode, table, cache_c, cache_p, tok,
              pos, 6, f"page_size={page_size}")


@pytest.mark.parametrize("arch", ["gemma2-9b", "recurrentgemma-2b"])
@pytest.mark.parametrize("backend", ["gather", "pallas_paged"])
def test_unrolled_decode_matches_scan(arch, backend):
    """The unrolled depth loop (the analysis form) indexes the stacked
    KV pools by a static group where the scan carries them with a traced
    index: both write the same rows into the same pages and return the
    same logits, with local and global pools per group (gemma2) and
    recurrent state beside a pool (recurrentgemma)."""
    (model, params, _, table, _, cache_p,
     tok, pos) = _build_pair(arch, (7, 10))
    for s in range(pos.shape[0]):
        cache_p, ok = table.prepare_step(cache_p, s, int(pos[s]))
        assert ok
    cache_p = table.flush(cache_p)
    unrolled = TransformerLM(model.cfg, unroll=True)
    outs = [jax.jit(functools.partial(m.decode_step, decode_backend=backend))(
        params, cache_p, jnp.asarray(tok), jnp.asarray(pos))
        for m in (model, unrolled)]
    (la, ca), (lb, cb) = outs
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               atol=1e-5, rtol=1e-5)
    assert jax.tree.structure(ca) == jax.tree.structure(cb)
    for a, b in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# offload / restore round trip
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "recurrentgemma-2b",
                                  "falcon-mamba-7b"])
def test_offload_round_trip_bit_exact(arch):
    """A slot's pages leave device memory (host offload) and re-enter —
    into different physical pool pages — bitwise unchanged, and the
    continued decode still matches the contiguous cache exactly."""
    (model, params, decode, table, cache_c, cache_p,
     tok, pos) = _build_pair(arch, (7, 10))
    cache_c, cache_p, tok, pos = _lockstep(
        model, params, decode, table, cache_c, cache_p, tok, pos, 3,
        f"{arch}: pre-offload")
    before = jax.tree.map(np.asarray, jax.tree.leaves(logical_view(cache_p)))

    cache_p, payload = table.offload(cache_p, 1, int(pos[1]))
    assert payload.tokens == int(pos[1])
    assert sum(k.nbytes + v.nbytes for _, k, v in payload.kv.values()) > 0 \
        or payload.state, "offload moved no bytes"
    # slot 1's rows are gone from the device view (block -> DUMP)...
    view_k = jax.tree.leaves(logical_view(cache_p))
    assert any(not np.array_equal(a, b) for a, b in zip(before, view_k))

    # ...and restore brings every page back bit-identically
    cache_p = table.restore(cache_p, 1, payload)
    after = jax.tree.map(np.asarray, jax.tree.leaves(logical_view(cache_p)))
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b, err_msg=f"{arch}: restore")
    _assert_views_equal(cache_c, cache_p, f"{arch}: post-restore")
    _lockstep(model, params, decode, table, cache_c, cache_p, tok, pos, 3,
              f"{arch}: post-restore decode")


def test_offload_unadmitted_slot_raises_named_error():
    """Offloading a slot that holds no pages (double preemption, or a
    scheduler bug picking a retired victim) must fail as a named
    :class:`PageTableError` carrying the slot, the stream, and the
    live-slot set — not as a bare ``KeyError`` out of the allocator's
    bookkeeping — and must not corrupt the table on the way out."""
    from repro.serve.paging import PageTableError

    (model, params, decode, table, cache_c, cache_p,
     tok, pos) = _build_pair("qwen1.5-0.5b", (7, 10))
    cache_p, payload = table.offload(cache_p, 1, int(pos[1]))
    with pytest.raises(PageTableError) as ei:
        table.offload(cache_p, 1, int(pos[1]))
    msg = str(ei.value)
    assert "slot 1 holds no pages" in msg
    assert "groups" in msg                     # the stream is named
    assert "live slots there: [0]" in msg      # the still-admitted set
    # the failed call mutated nothing: restore + decode stay bit-exact
    cache_p = table.restore(cache_p, 1, payload)
    _assert_views_equal(cache_c, cache_p, "post-error restore")
    _lockstep(model, params, decode, table, cache_c, cache_p, tok, pos, 2,
              "qwen1.5-0.5b: post-error decode")


def test_prepare_step_commits_partial_progress_and_retry_is_exact():
    """Pool exhaustion mid-``prepare_step``: assignments for streams
    visited before the exhausted one stay committed (the documented
    invariant) — the retry after pages free up skips them, allocates
    only the missing streams, and the continued decode stays
    bit-identical to the contiguous cache, i.e. to a serve that never
    exhausted the pool."""
    (model, params, decode, table, cache_c, cache_p,
     tok, pos) = _build_pair("gemma2-9b", (3, 10))
    local, glob = [st for st in table.streams if not st.is_state]
    assert local.kind == "local" and glob.kind == "global"
    # pos 5 crosses a page boundary in BOTH streams for slot 0; empty
    # the global stream's free list so the local assignment commits and
    # the global one exhausts
    stolen, glob.free[0] = glob.free[0], []
    cache_p, ok = table.prepare_step(cache_p, 0, 5)
    assert not ok
    assert 1 in local.slot_pages[0]        # partial progress committed
    assert 1 not in glob.slot_pages[0]
    committed = local.slot_pages[0][1]
    # a victim's pages return (engine preemption) -> the retry
    # succeeds, reusing the committed page instead of re-allocating
    glob.free[0] = stolen
    cache_p, ok = table.prepare_step(cache_p, 0, 5)
    assert ok
    assert local.slot_pages[0][1] == committed
    assert 1 in glob.slot_pages[0]
    _lockstep(model, params, decode, table, cache_c, cache_p, tok, pos, 4,
              "gemma2-9b: post-retry decode")


# ---------------------------------------------------------------------------
# engine level: past-max_len decode, preemption, all archs
# ---------------------------------------------------------------------------
def _engine_pair(arch, paged_kw, ref_max_len, max_batch=2):
    cfg = get_config(arch, smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    paged_max_len = paged_kw.pop("_max_len", ref_max_len)
    ref = ServeEngine(model, params, max_len=ref_max_len,
                      max_batch=max_batch)
    pag = ServeEngine(model, params, max_len=paged_max_len,
                      max_batch=max_batch,
                      paged=PagedCacheConfig(**paged_kw))
    return cfg, ref, pag


def test_decode_past_contiguous_max_len():
    """Acceptance: a request whose prompt+generation exceeds the old
    contiguous per-slot cap completes through paged decode — and
    matches a big-contiguous-cache engine bit-for-bit (the prefill
    bucket cap stays at 8 while decode grows to 28 tokens)."""
    cfg, ref, pag = _engine_pair(
        "qwen1.5-0.5b",
        {"page_size": 4, "max_ctx": 32, "_max_len": 8}, ref_max_len=32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 3, 8)]
    a = ref.serve(prompts, 20, seed=5)
    b = pag.serve(prompts, 20, seed=5)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.shape[0] == 20          # past the old max_len=8 cap
        np.testing.assert_array_equal(x, y, err_msg=f"request {i}")


@pytest.mark.slow_serve
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_paged_engine_matches_contiguous_all_archs(arch):
    """Acceptance: on every arch, a tight-budget paged engine (growth
    past the prefill cap + forced preemption/offload) serves a mixed
    greedy+stochastic workload bit-identically to an ample contiguous
    engine."""
    cfg, ref, pag = _engine_pair(
        arch, {"page_size": 8, "max_ctx": 32, "resident_pages": 6,
               "_max_len": 16}, ref_max_len=32)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 3)]
    temps, topks = [0.0, 50.0, 50.0], [None, None, 5]
    a = ref.serve(prompts, 20, temperature=temps, top_k=topks, seed=11)
    b = pag.serve(prompts, 20, temperature=temps, top_k=topks, seed=11)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(x, y, err_msg=f"{arch} request {i}")


def _count_calls(obj, attr):
    """Wrap ``obj.attr`` on the instance; returns the list of calls."""
    calls, fn = [], getattr(obj, attr)

    def wrapped(*a, **k):
        calls.append(a)
        return fn(*a, **k)
    setattr(obj, attr, wrapped)
    return calls


def test_decode_applies_assignments_with_no_program_between_steps():
    """An unpressured paged serve grows through page assignments that
    the decode step applies: the flush program never runs, and the
    tokens match the contiguous engine's."""
    cfg, ref, pag = _engine_pair(
        "qwen1.5-0.5b", {"page_size": 4, "max_ctx": 32, "_max_len": 16},
        ref_max_len=32)
    table = pag.page_table
    flushes = _count_calls(table, "_flush_jit")
    assigns = _count_calls(table, "fold_pending")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 3)]
    a = ref.serve(prompts, 14, seed=2)
    b = pag.serve(prompts, 14, seed=2)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(x, y, err_msg=f"request {i}")
    assert flushes == []
    assert len(assigns) == 13             # one hand-off a decode step


@pytest.mark.parametrize("arch,plens,kw", [
    ("qwen1.5-0.5b", (5, 9, 3), dict(page_size=4, resident_pages=8)),
    # gemma2's local ring and global streams: a grower that takes its
    # local page and finds no global one is the newest slot, preempts
    # itself and is offloaded holding its pending local assignment
    ("gemma2-9b", (16, 7, 3), dict(page_size=4, resident_pages=9)),
])
def test_preempted_serve_matches_unconstrained_and_contiguous(arch, plens,
                                                              kw):
    """Preemption under a tight budget meets assignments still pending
    (the flush program applies them before the offload): the tokens
    match an unconstrained paged serve and the contiguous engine."""
    cfg, ref, tight = _engine_pair(
        arch, dict(kw, max_ctx=32, _max_len=16), ref_max_len=32,
        max_batch=3)
    ample = ServeEngine(ref.model, ref.params, max_len=16, max_batch=3,
                        paged=PagedCacheConfig(page_size=kw["page_size"],
                                               max_ctx=32))
    table = tight.page_table
    pending_victims = []
    offload = table.offload

    def watched(cache, slot, tokens):
        pending_victims.append(
            bool((table._pending[0, :, slot] != NO_PAGE).any()))
        return offload(cache, slot, tokens)
    table.offload = watched
    flushes = _count_calls(table, "_flush_jit")
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in plens]
    outs = [e.serve(prompts, 14, seed=11) for e in (ref, ample, tight)]
    for i, (x, y, z) in enumerate(zip(*outs)):
        np.testing.assert_array_equal(x, y, err_msg=f"{arch} ample {i}")
        np.testing.assert_array_equal(x, z, err_msg=f"{arch} tight {i}")
    assert pending_victims and flushes       # preempted over pending rows
    if arch == "gemma2-9b":
        assert any(pending_victims)


def test_assignment_zeroes_one_page_and_writes_one_block_entry(monkeypatch):
    """The assignment rule with one real entry and 31 ``NO_PAGE`` ones
    runs its loop once, zeroes that page in every layer and points one
    block entry at it; every other page and entry is untouched.  The
    flush program applies the same rows to the same result."""
    model, *_ = _arch("qwen1.5-0.5b")
    table = PageTable(model, max_batch=32, max_ctx=MAX_CTX, page_size=PAGE)
    cache = table.init_cache()
    node = cache["groups"][0]
    rng = np.random.default_rng(0)
    node = dataclasses.replace(
        node, kp=jnp.asarray(rng.normal(size=node.kp.shape), node.kp.dtype),
        vp=jnp.asarray(rng.normal(size=node.vp.shape), node.vp.dtype))
    cache = {**cache, "groups": (node,) + cache["groups"][1:]}
    rows = np.full((2, 1, 32), NO_PAGE, np.int32)
    rows[:, 0, 7] = 12, 2                       # slot 7: page 12 at jdx 2
    before = jax.tree.map(np.asarray, node)
    out = jax.jit(table.apply_assignments)(cache, rows[0], rows[1])
    after = out["groups"][0]
    for name in ("kp", "vp"):
        a, b = np.asarray(getattr(after, name)), getattr(before, name)
        assert not a[:, 12].any()
        np.testing.assert_array_equal(np.delete(a, 12, axis=1),
                                      np.delete(b, 12, axis=1))
    block = np.array(after.block)
    assert (block[:, 7, 2] == 12).all()
    block[:, 7, 2] = before.block[:, 7, 2]
    np.testing.assert_array_equal(block, before.block)
    # the loop's trip count, read where it is set (eagerly, so concrete)
    trips, loop = [], jax.lax.fori_loop

    def counted(lo, hi, body, init):
        trips.append(int(hi) - int(lo))
        return loop(lo, hi, body, init)
    monkeypatch.setattr(jax.lax, "fori_loop", counted)
    eager = table.apply_assignments(cache, jnp.asarray(rows[0]),
                                    jnp.asarray(rows[1]))
    monkeypatch.undo()
    assert trips == [1]
    for x, y in zip(jax.tree.leaves(eager), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    flushed = table._flush_jit(jax.tree.map(jnp.copy, cache), rows)
    for x, y in zip(jax.tree.leaves(flushed), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# telemetry: page traffic + exact-sum invariant
# ---------------------------------------------------------------------------
class _RecordingTelemetry(ServeTelemetry):
    """Keeps the raw event stream so the test can re-derive every byte
    independently of the accumulator implementation."""

    def __init__(self, traffic, **kw):
        super().__init__(traffic, **kw)
        self.events = []

    def record_prefill(self, plen, dt=0.0, padded_len=None):
        self.events.append(("prefill", plen, padded_len))
        super().record_prefill(plen, dt, padded_len=padded_len)

    def record_decode(self, ctx_lengths, dt=0.0):
        self.events.append(("decode", tuple(int(c) for c in ctx_lengths)))
        super().record_decode(ctx_lengths, dt)

    def record_page_out(self, ctx):
        self.events.append(("page_out", int(ctx)))
        super().record_page_out(ctx)

    def record_page_in(self, ctx):
        self.events.append(("page_in", int(ctx)))
        super().record_page_in(ctx)


def test_telemetry_page_bytes_and_exact_invariant():
    """Acceptance: page-in/page-out bytes are nonzero when the
    resident-page budget forces offload, they flow into the
    WorkloadProfile, and the profile equals the per-event byte sums
    EXACTLY — decode traffic from decode events only (prefill pad waste
    is never double-counted into DRAM bytes).  The engine's gather
    backend additionally pays the materialized logical view per live
    slot per step (the phantom traffic the pallas_paged kernel
    removes), which the reconstruction must reproduce too."""
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(
        model, params, max_len=48, max_batch=3,
        paged=PagedCacheConfig(page_size=8, resident_pages=8))
    t = TrafficModel.from_config(get_config("qwen1.5-0.5b"), max_len=4096,
                                 page_size=8)
    tele = _RecordingTelemetry(t)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 3)]
    engine.serve(prompts, 30, telemetry=tele)
    assert tele.decode_mode == "gather"   # engine-configured

    # the tight budget forced offload traffic, and it reached the profile
    assert tele.page_outs > 0 and tele.page_ins > 0
    assert tele.page_out_bytes_total > 0 and tele.page_in_bytes_total > 0

    # independent per-event reconstruction
    param_total = kv_total = write_total = po_total = pi_total = 0
    gr_total = gw_total = 0
    n_steps = 0
    for ev in tele.events:
        if ev[0] == "decode":
            ctx = ev[1]
            n_steps += 1
            param_total += t.param_read_bytes
            kv_total += t.state_bytes * len(ctx) \
                + sum(t.kv_read_bytes(c) for c in ctx)
            write_total += (t.kv_write_bytes + t.state_bytes) * len(ctx)
            gr_total += t.gather_view_read_bytes * len(ctx)
            gw_total += t.gather_view_write_bytes * len(ctx)
        elif ev[0] == "page_out":
            po_total += t.page_bytes(ev[1])
        elif ev[0] == "page_in":
            pi_total += t.page_bytes(ev[1])
    assert n_steps == tele.decode_steps
    assert po_total == tele.page_out_bytes_total
    assert pi_total == tele.page_in_bytes_total
    assert gr_total == tele.gather_read_bytes_total
    assert gw_total == tele.gather_write_bytes_total

    w = tele.workload_profile(step_period_s=0.01)
    n = tele.decode_steps
    assert w.read_bytes_per_iter == \
        param_total / n + kv_total / n + gr_total / n + po_total / n
    assert w.write_bytes_per_iter == \
        write_total / n + gw_total / n + pi_total / n

    # page moves are whole pages: ctx 5 rounds up to one 8-token page
    # per global layer (+ state); never less than the row-exact bytes
    exact = dataclasses.replace(t, page_size=0)
    assert t.page_bytes(5) >= exact.page_bytes(5)
    assert t.page_bytes(5) == exact.page_bytes(8)


def test_paged_telemetry_zero_without_pressure():
    """An ample budget never offloads: page counters stay zero.  The
    gather backend still pays its materialized-view traffic every step
    (pressure-independent — that's why the kernel backend exists), and
    pinning ``decode_mode="contiguous"`` recovers the row-exact
    profile."""
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, max_len=32, max_batch=2,
                         paged=PagedCacheConfig(page_size=8))
    t = TrafficModel.from_config(get_config("qwen1.5-0.5b"), max_len=4096)
    tele = ServeTelemetry(t)
    pinned = ServeTelemetry(t, decode_mode="contiguous")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    engine.serve([prompt], 6, telemetry=tele)
    engine.serve([prompt], 6, telemetry=pinned)
    for s in (tele, pinned):
        assert s.page_outs == s.page_ins == 0
        assert s.page_out_bytes_total == s.page_in_bytes_total == 0
    # engine-configured gather accounting: one view read+write per live
    # slot per step on top of the row-exact sweep
    assert tele.decode_mode == "gather"
    n = tele.decode_steps
    assert tele.gather_read_bytes_total == n * t.gather_view_read_bytes
    assert tele.gather_write_bytes_total == n * t.gather_view_write_bytes
    w = tele.workload_profile(step_period_s=0.01)
    assert w.read_bytes_per_iter == \
        (tele.param_read_bytes_total + tele.kv_read_bytes_total
         + tele.gather_read_bytes_total) / n
    # the pinned sink keeps the seed (row-exact) accounting
    assert pinned.decode_mode == "contiguous"
    assert pinned.gather_read_bytes_total == 0
    wp = pinned.workload_profile(step_period_s=0.01)
    assert wp.read_bytes_per_iter == \
        (pinned.param_read_bytes_total + pinned.kv_read_bytes_total) \
        / pinned.decode_steps


# ---------------------------------------------------------------------------
# PageTable policy
# ---------------------------------------------------------------------------
def test_page_table_budget_floor():
    """A budget that cannot hold one fully decoded slot is rejected at
    construction (it could deadlock with every other slot offloaded)."""
    model, params, *_ = _arch("qwen1.5-0.5b")
    with pytest.raises(ValueError, match="resident_pages"):
        PageTable(model, max_batch=2, max_ctx=MAX_CTX, page_size=8,
                  resident_pages=2)   # needs ceil(24/8) = 3
    with pytest.raises(ValueError, match="page_size"):
        PageTable(model, max_batch=2, max_ctx=MAX_CTX, page_size=0)
    with pytest.raises(ValueError, match="max_ctx"):
        ServeEngine(model, params, max_len=32, max_batch=1,
                    paged=PagedCacheConfig(page_size=8, max_ctx=16))


def test_paged_config_validates_eagerly():
    """A bad PagedCacheConfig fails at construction / engine entry with
    the offending field named — never deep inside PageTable after the
    prefill executables already lowered."""
    with pytest.raises(ValueError, match="PagedCacheConfig.page_size"):
        PagedCacheConfig(page_size=0)
    with pytest.raises(ValueError, match="PagedCacheConfig.resident_pages"):
        PagedCacheConfig(resident_pages=0)
    with pytest.raises(ValueError, match="PagedCacheConfig.max_ctx"):
        PagedCacheConfig(max_ctx=-4)

    model, params, *_ = _arch("qwen1.5-0.5b")
    cfg = model.cfg
    bad = PagedCacheConfig(page_size=8, resident_pages=2, max_ctx=MAX_CTX)
    # the floor needs the model's layer mix: validate() names the field
    with pytest.raises(ValueError, match="PagedCacheConfig.resident_pages"):
        bad.validate(cfg)
    assert bad.slot_floor(cfg, MAX_CTX) == 3     # ceil(24/8)
    # the engine applies the same check before lowering anything: abuse
    # abstract params — if validation were lazy, tracing would fail
    # first with an unrelated error
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    with pytest.raises(ValueError, match="PagedCacheConfig.resident_pages"):
        ServeEngine(model, shapes, max_len=16, max_batch=2, paged=bad)
    # a config with no max_ctx anywhere cannot be validated
    with pytest.raises(ValueError, match="max_ctx"):
        PagedCacheConfig(page_size=8).validate(cfg)


def test_allocate_on_write_and_free_on_retire():
    """Admission takes exactly ceil(min(plen, cache_len)/page) pages per
    KV stream (+1 state page per recurrent stream); retire returns
    every page to the free list."""
    model, params, prefill, _, _, table = _arch("recurrentgemma-2b")
    table.reset()
    cache = table.init_cache()
    free0 = table.free_page_counts()
    row = np.arange(7, dtype=np.int32) % model.cfg.vocab_size
    _, one = _prefill_slot(model, params, prefill, row)
    cache = table.admit(cache, one, 0, 7)
    for stream in table.streams:
        held = stream.slot_pages[0]
        if stream.is_state:
            assert isinstance(held, int)
        else:
            # window=8 ring, PAGE=5: 7 rows -> 2 pages; global would
            # also take 2 (ceil(7/5))
            assert len(held) == -(-min(7, stream.cache_len) // PAGE)
    cache = table.release(cache, 0)
    assert table.free_page_counts() == free0
    assert all(not s.slot_pages for s in table.streams)
