"""Prefix-sharing / copy-on-write acceptance suite (PR 10).

The contract under test: with ``PagedCacheConfig(sharing=...)`` the
engine serves any workload **bit-identically** to the unshared engine
(same outputs, same lowered executables) while N same-prefix requests
allocate the shared prefix pages **once** — the saving shows up in the
page table's allocation stats, in telemetry's ``prefix_hit`` traffic
class (whose exact-sum invariant against the unshared total is pinned
here, including across preempt/restore), and in the page-access trace's
per-step row set.  Also covers the duplicate-request-id rejection.
"""
import collections

import jax
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core.trace import PageAccessTrace
from repro.models.transformer import TransformerLM
from repro.serve import (PagedCacheConfig, PrefixSharingConfig, ServeEngine,
                         ServeTelemetry, TrafficModel)
from repro.serve.paging import prefix_page_keys

PAGE = 8

_CACHED = {}


def _arch(arch):
    if arch not in _CACHED:
        cfg = get_config(arch, smoke=True)
        model = TransformerLM(cfg)
        params = model.init(jax.random.key(0))
        _CACHED[arch] = (cfg, model, params)
    return _CACHED[arch]


def _engine(arch, sharing, *, max_len=32, max_batch=3, max_ctx=32,
            resident_pages=None, page_size=PAGE):
    cfg, model, params = _arch(arch)
    return cfg, ServeEngine(
        model, params, max_len=max_len, max_batch=max_batch,
        paged=PagedCacheConfig(page_size=page_size, max_ctx=max_ctx,
                               resident_pages=resident_pages,
                               sharing=sharing))


def _tele(cfg, **kw):
    return ServeTelemetry(
        TrafficModel.from_config(cfg, max_len=32, page_size=PAGE), **kw)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# hash scheme
# ---------------------------------------------------------------------------
def test_prefix_keys_chain_properties():
    """Chained content hashing: a page key covers its whole prefix."""
    rng = np.random.default_rng(1)
    a = rng.integers(0, 1000, (20,)).astype(np.int32)
    ka = prefix_page_keys(a, 8)
    # deterministic
    assert prefix_page_keys(a.copy(), 8) == ka
    assert len(ka.full) == 2 and ka.tail is not None
    assert ka.whole != ka.tail or ka.whole == ka.tail  # whole defined
    assert ka.group == ka.full[0]
    # same first page, divergent second page: full[0] shared, rest not
    b = a.copy()
    b[10] += 1
    kb = prefix_page_keys(b, 8)
    assert kb.full[0] == ka.full[0]
    assert kb.full[1] != ka.full[1] and kb.tail != ka.tail
    assert kb.whole != ka.whole
    # chaining: a change inside page 0 invalidates EVERY later key
    c = a.copy()
    c[0] += 1
    kc = prefix_page_keys(c, 8)
    assert kc.full[0] != ka.full[0] and kc.full[1] != ka.full[1]
    assert kc.tail != ka.tail and kc.group != ka.group
    # a strict prefix extension shares all full-page keys
    kd = prefix_page_keys(a[:19], 8)
    assert kd.full == ka.full and kd.tail != ka.tail


def test_prefix_keys_short_and_aligned():
    toks = np.arange(5, dtype=np.int32)
    k = prefix_page_keys(toks, 8)        # shorter than one page
    assert k.full == () and k.tail is not None
    assert k.whole == k.tail and k.group == k.whole
    ka = prefix_page_keys(np.arange(16, dtype=np.int32), 8)  # aligned
    assert len(ka.full) == 2 and ka.tail is None
    assert ka.whole == ka.full[-1]


# ---------------------------------------------------------------------------
# allocation-once pin + bit identity
# ---------------------------------------------------------------------------
def test_same_prefix_allocates_prefix_pages_once():
    """Acceptance pin: N identical prompts register each physical page
    once; the other N-1 requests *attach* (refcount) instead of
    allocating, and first-write-past-shared forks private copies."""
    cfg, solo = _engine("qwen1.5-0.5b",
                        PrefixSharingConfig(memo_size=0), max_batch=3)
    prompt = _prompts(cfg, [12], seed=2)[0]
    solo.serve([prompt], 4, seed=1)
    s1 = dict(solo.page_table.stats)
    assert s1["pages_registered"] > 0 and s1["pages_attached"] == 0

    cfg, eng = _engine("qwen1.5-0.5b",
                       PrefixSharingConfig(memo_size=0), max_batch=3)
    out = eng.serve([prompt, prompt.copy(), prompt.copy()], 4, seed=1)
    s3 = dict(eng.page_table.stats)
    # the prefix pages were allocated exactly once...
    assert s3["pages_registered"] == s1["pages_registered"]
    # ...and attached by each of the two duplicate admissions
    assert s3["pages_attached"] == 2 * s1["pages_registered"]
    # decode past the shared region forked private tail copies
    assert s3["cow_forks"] > 0
    # duplicates generate identically (greedy default w/ seed applies
    # per-request keys only at temperature>0; these are greedy)
    np.testing.assert_array_equal(out[0], out[1])
    np.testing.assert_array_equal(out[0], out[2])


def _paired_serve(arch, lens, *, dup=True, temps=None, topks=None,
                  max_new=12, seed=11, sharing=None, resident_pages=None,
                  max_batch=3):
    cfg, off = _engine(arch, None, max_batch=max_batch,
                       resident_pages=resident_pages)
    cfg, on = _engine(arch, sharing or PrefixSharingConfig(),
                      max_batch=max_batch, resident_pages=resident_pages)
    prompts = _prompts(cfg, lens, seed=3)
    if dup:
        prompts[1] = prompts[0].copy()       # exact duplicate
        if len(prompts) > 2 and len(prompts[0]) > 2:
            prompts[2] = prompts[0][:len(prompts[0]) - 1].copy()
    kw = dict(temperature=temps, top_k=topks, seed=seed)
    a = off.serve(prompts, max_new, **kw)
    b = on.serve(prompts, max_new, **kw)
    return cfg, off, on, a, b


def test_sharing_bit_identical_qwen():
    """Sharing on vs off: identical outputs on a shared-prefix workload
    (one exact duplicate + one strict-prefix prompt + one unique), with
    the lowered prefill-executable count pinned equal."""
    cfg, off, on, a, b = _paired_serve(
        "qwen1.5-0.5b", [12, 12, 11, 5],
        temps=[0.0, 50.0, 50.0, 0.0], topks=[None, None, 5, None])
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(x, y, err_msg=f"request {i}")
    assert on.prefill_executables == off.prefill_executables
    assert on.page_table.stats["pages_attached"] > 0


@pytest.mark.slow_serve
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_sharing_bit_identical_all_archs(arch):
    """Acceptance: shared-prefix serving is bit-identical to unshared
    on every architecture (state archs and sub-page local windows must
    degrade silently, never perturb)."""
    cfg, off, on, a, b = _paired_serve(
        arch, [12, 12, 11, 5], temps=[0.0, 50.0, 50.0, 0.0],
        topks=[None, None, 5, None])
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(x, y, err_msg=f"{arch} request {i}")
    assert on.prefill_executables == off.prefill_executables


def test_state_arch_degrades_silently():
    """recurrentgemma's recurrent state is rewritten every step and its
    smoke local windows are shorter than these prompts, so sharing must
    engage nothing — and change nothing."""
    cfg, off, on, a, b = _paired_serve(
        "recurrentgemma-2b", [12, 12, 10], temps=[0.0, 50.0, 0.0])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    st = on.page_table.stats
    assert st["pages_attached"] == 0 and st["cow_forks"] == 0


# ---------------------------------------------------------------------------
# full skip (whole-prompt memo)
# ---------------------------------------------------------------------------
def test_full_skip_on_exact_duplicate():
    """An exact duplicate prompt skips prefill entirely: every page
    attaches, the memoized logits replay, and the generation matches
    the first request's (greedy) without a second prefill dispatch."""
    cfg, eng = _engine("qwen1.5-0.5b", PrefixSharingConfig(), max_batch=2)
    prompt = _prompts(cfg, [12], seed=4)[0]
    tele = _tele(cfg)
    out = eng.serve([prompt, prompt.copy()], 8, telemetry=tele, seed=9)
    np.testing.assert_array_equal(out[0], out[1])
    assert tele.prefix_full_skips == 1
    assert eng.page_table.stats["full_attaches"] == 1
    # one bucket shape ever prefilled -> exactly one lowered executable
    assert eng.prefill_executables == 1
    # telemetry still books the skipped prefill's request accounting
    assert tele.n_prefills == 2


def test_cow_fork_without_memo():
    """With the memo disabled, duplicates dedup-attach and the first
    append past the shared region triggers a copy-on-write fork; the
    generation stays bit-identical to the unshared engine."""
    cfg, off, on, a, b = _paired_serve(
        "qwen1.5-0.5b", [11, 11], temps=[50.0, 50.0],
        sharing=PrefixSharingConfig(memo_size=0), max_batch=2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    st = on.page_table.stats
    assert st["pages_attached"] > 0 and st["cow_forks"] > 0
    assert st["full_attaches"] == 0


# ---------------------------------------------------------------------------
# telemetry exact-sum invariant
# ---------------------------------------------------------------------------
class _AdmitRecorder(ServeTelemetry):
    def __init__(self, traffic, **kw):
        super().__init__(traffic, **kw)
        self.admits = []

    def record_admit_shared(self, plen, hit_layer_tokens, total_layer_tokens,
                            **kw):
        self.admits.append((plen, hit_layer_tokens, total_layer_tokens))
        super().record_admit_shared(plen, hit_layer_tokens,
                                    total_layer_tokens, **kw)


def test_telemetry_exact_sum_invariant():
    """Acceptance: hit bytes + computed (written) bytes == the unshared
    total, per admission and in aggregate — sharing re-classifies
    admission traffic, it never changes the sum."""
    cfg, eng = _engine("qwen1.5-0.5b",
                       PrefixSharingConfig(memo_size=0), max_batch=3)
    t = TrafficModel.from_config(cfg, max_len=32, page_size=PAGE)
    shared = _AdmitRecorder(t)
    prompts = _prompts(cfg, [12, 12, 9], seed=5)
    prompts[1] = prompts[0].copy()
    eng.serve(prompts, 6, telemetry=shared, seed=2)

    # same lengths, all-unique content: every page misses
    cfg, eng2 = _engine("qwen1.5-0.5b",
                        PrefixSharingConfig(memo_size=0), max_batch=3)
    unshared = _AdmitRecorder(t)
    eng2.serve(_prompts(cfg, [12, 12, 9], seed=6), 6,
               telemetry=unshared, seed=2)

    assert shared.prefix_hit_tokens > 0
    assert unshared.prefix_hit_tokens == 0
    # per admission: hit never exceeds total
    for plen, hit, total in shared.admits:
        assert 0 <= hit <= total
    # aggregate exact sum: (hit + written) bytes invariant across the
    # two runs because the per-request totals depend only on lengths
    assert (shared.prefix_hit_bytes_total + shared.admit_write_bytes_total
            == unshared.prefix_hit_bytes_total
            + unshared.admit_write_bytes_total)
    assert shared.prefix_hit_frac > 0.0


def test_no_double_count_across_preempt_restore():
    """A preempted-and-restored shared slot must not re-book admission
    traffic: exactly one record_admit_shared per request, and the
    exact-sum matches an ample-budget run of the same workload."""
    cfg, _, _ = _arch("qwen1.5-0.5b")
    prompts = _prompts(cfg, [12, 12, 9], seed=5)
    prompts[1] = prompts[0].copy()

    def run(resident_pages):
        cfg2, eng = _engine("qwen1.5-0.5b",
                            PrefixSharingConfig(memo_size=0), max_batch=3,
                            resident_pages=resident_pages)
        tele = _AdmitRecorder(
            TrafficModel.from_config(cfg2, max_len=32, page_size=PAGE))
        out = eng.serve(prompts, 14, seed=2, telemetry=tele)
        return out, tele

    ample_out, ample = run(None)
    tight_out, tight = run(6)            # forces preemption + offload
    assert tight.page_outs > 0 and tight.page_ins > 0
    for x, y in zip(ample_out, tight_out):
        np.testing.assert_array_equal(x, y)
    assert len(tight.admits) == len(prompts) == tight.prefix_admits
    assert (tight.prefix_hit_bytes_total + tight.admit_write_bytes_total
            == ample.prefix_hit_bytes_total + ample.admit_write_bytes_total)


def test_record_admit_shared_rejects_overcount():
    cfg, _, _ = _arch("qwen1.5-0.5b")
    tele = _tele(cfg)
    with pytest.raises(ValueError):
        tele.record_admit_shared(8, hit_layer_tokens=10, total_layer_tokens=9)


# ---------------------------------------------------------------------------
# request ids
# ---------------------------------------------------------------------------
def test_duplicate_request_ids_rejected():
    cfg, eng = _engine("qwen1.5-0.5b", None)
    prompts = _prompts(cfg, [5, 6, 7], seed=7)
    with pytest.raises(ValueError, match=r"indices 0 and 2"):
        eng.serve(prompts, 4, request_ids=[9, 3, 9])


def test_custom_request_ids_keep_input_order():
    """Out-of-order ids must not change scheduling outcomes: greedy
    outputs (sampling-key independent) under a tight budget match the
    default-id run, in input order — victim selection follows arrival
    order, not id order."""
    cfg, eng = _engine("qwen1.5-0.5b", None, resident_pages=6)
    prompts = _prompts(cfg, [12, 9, 11], seed=8)
    a = eng.serve(prompts, 14, seed=1)
    cfg, eng2 = _engine("qwen1.5-0.5b", None, resident_pages=6)
    b = eng2.serve(prompts, 14, seed=1, request_ids=[100, 5, 50])
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(x, y, err_msg=f"request {i}")


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------
def test_prefix_schedule_batches_same_prefix():
    """On an interleaved two-group workload with a 2-slot batch, FIFO
    serves A,B then A,B (the group's pages die between batches —
    sharing is in-flight only), while the prefix schedule co-schedules
    A,A then B,B and actually attaches.  Outputs are schedule-
    independent (greedy)."""
    cfg, _, _ = _arch("qwen1.5-0.5b")
    base = _prompts(cfg, [12, 12], seed=9)
    prompts = [base[0], base[1], base[0].copy(), base[1].copy()]

    def run(schedule):
        cfg2, eng = _engine(
            "qwen1.5-0.5b",
            PrefixSharingConfig(schedule=schedule, memo_size=0),
            max_batch=2)
        out = eng.serve(prompts, 6, seed=3)
        return out, dict(eng.page_table.stats)

    out_f, st_f = run("fifo")
    out_p, st_p = run("prefix")
    assert st_p["pages_attached"] > st_f["pages_attached"]
    for x, y in zip(out_f, out_p):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# trace row set
# ---------------------------------------------------------------------------
def test_trace_row_set_shrinks_under_sharing():
    """The page-access trace dedups physical ids per step, so the
    shared serve's footprint and per-step touch totals are strictly
    smaller than its unshared twin's on a duplicate-prompt workload."""
    cfg, _, _ = _arch("qwen1.5-0.5b")
    prompts = _prompts(cfg, [12], seed=10)
    prompts = [prompts[0], prompts[0].copy()]

    def run(sharing):
        cfg2, eng = _engine("qwen1.5-0.5b", sharing, max_batch=2)
        trace = PageAccessTrace(eng.page_table.stream_names())
        tele = _tele(cfg2, trace=trace)
        out = eng.serve(prompts, 6, seed=4, telemetry=tele)
        return out, trace

    out_u, tr_u = run(None)
    out_s, tr_s = run(PrefixSharingConfig(memo_size=0))
    for x, y in zip(out_u, out_s):
        np.testing.assert_array_equal(x, y)
    assert tr_s.n_steps == tr_u.n_steps
    assert sum(tr_s.pages_touched()) < sum(tr_u.pages_touched())
    assert sum(tr_s.step_page_counts()) < sum(tr_u.step_page_counts())
    assert all(a <= b for a, b in zip(tr_s.step_page_counts(),
                                      tr_u.step_page_counts()))

