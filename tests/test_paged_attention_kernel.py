"""Parity suite for the block-table paged decode-attention kernel.

Contract under test, at three altitudes:

* **op level** — ``paged_attention(backend="pallas")`` (the Pallas
  kernel, interpret mode) matches ``backend="ref"`` (gather + dense
  softmax) over page sizes that do and don't divide the cache length
  (partial tail pages), ring wrap-around, per-slot positions, sliding
  windows (including windows smaller than one page and windows that
  start mid-block), and softcap; over blocks of several pages (pool
  widths whose pages make several blocks a slot, a last block cut short)
  at positions whose live pages are one, two, one block, one block and
  a page, and all; and over pools whose pages no valid row maps to hold
  NaN.
* **model level** — ``decode_step(..., decode_backend="pallas_paged")``
  on a paged cache tracks both the gather backend and the contiguous
  cache across lockstep greedy decoding on ALL 10 archs: logits agree
  to interpret-mode accumulation tolerance (the kernel's online
  softmax sums pages sequentially; the gather path reduces over the
  full row — documented, not a defect) and the sampled tokens are
  IDENTICAL, including across page-growth boundaries and
  post-preemption (offload/restore) resume.
* **engine level** — ``ServeEngine(decode_backend="pallas_paged")``
  serves every arch with generations identical to the gather engine
  (the PR's acceptance criterion), and telemetry accounts only true
  per-page reads on the kernel path — zero materialized-view traffic.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from repro.configs import ARCH_IDS, get_config
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention.ops import paged_attention
from repro.models.transformer import TransformerLM
from repro.serve import (PagedCacheConfig, PageTable, ServeEngine,
                         ServeTelemetry, TrafficModel)

# Interpret-mode tolerance: the kernel accumulates the softmax online
# over pages while the oracle reduces over the whole row at once, so
# f32 results differ by accumulation order only.
TOL = 2e-4

MAX_CTX = 24
BUCKET = 16
PAGE = 5          # deliberately not a divisor of MAX_CTX or any window


# ---------------------------------------------------------------------------
# op level: kernel vs gather oracle
# ---------------------------------------------------------------------------
OP_CASES = [
    # b, kvh, g, hd, page, cache_len, window, softcap
    (2, 2, 2, 16, 5, 24, None, None),     # partial tail page
    (3, 1, 4, 8, 3, 10, 8, 30.0),         # window + softcap, GQA 4
    (1, 2, 1, 32, 4, 16, 5, None),        # window > page? no: 5 > 4
    (2, 4, 2, 16, 2, 7, 3, None),         # window smaller than 2 pages
    (1, 1, 1, 8, 1, 6, 1, None),          # row-granular pages, window=1
    (2, 2, 3, 16, 24, 24, None, 50.0),    # one whole-cache page
    # pools wide enough for several pages a block (f32 pages of 16 rows)
    (2, 1, 1, 1024, 16, 160, None, None),  # F 1024, g 1: 8-page blocks of 10
    (2, 2, 6, 128, 16, 640, 72, 30.0),    # F 256, g 6: 32 of 40, window
    (3, 2, 2, 256, 16, 320, 40, None),    # F 512: 16 of 20, window 40
]


def _slot_positions(rng, b, page, L, width):
    """``b`` random positions straddling the ring boundary (pos >= L
    wraps), then positions whose live pages are one, two, one block,
    one block and a page, and all, then two past the wrap."""
    ppb = paged_kernel.pages_per_block(page, width, 4, -(-L // page))
    edges = [0, page, ppb * page - 1, ppb * page, L - 1, L + ppb * page,
             2 * L - 1]
    return np.concatenate([rng.integers(0, 2 * L, (b,)), edges])


@pytest.mark.parametrize("b,kvh,g,hd,page,L,window,softcap", OP_CASES)
def test_kernel_matches_gather_oracle(b, kvh, g, hd, page, L, window,
                                      softcap, rng):
    pos = jnp.asarray(_slot_positions(rng, b, page, L, kvh * hd), jnp.int32)
    b = pos.shape[0]
    n_lp = -(-L // page)
    n_pages = 2 + b * n_lp + 3
    q = jnp.asarray(rng.standard_normal((b, kvh, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((n_pages, page, kvh * hd)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, page, kvh * hd)),
                     jnp.float32)
    block = jnp.asarray(
        rng.permutation(np.arange(2, n_pages))[:b * n_lp].reshape(b, n_lp),
        jnp.int32)
    ref = paged_attention(q, kp, vp, block, pos, cache_len=L, window=window,
                          softcap=softcap, backend="ref")
    pal = paged_attention(q, kp, vp, block, pos, cache_len=L, window=window,
                          softcap=softcap, backend="pallas")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


STACKED_CASES = [
    # layers, b, kvh, g, hd, page, cache_len, window, softcap
    (3, 2, 2, 3, 16, 5, 24, None, None),  # GQA 3, partial tail page
    (4, 3, 2, 2, 8, 4, 16, 6, 30.0),      # window + softcap
    (2, 2, 4, 1, 16, 3, 10, None, 50.0),  # MHA, ring wrap
    (2, 2, 2, 1, 256, 16, 320, 40, None),  # F 512: 16-page blocks of 20
]


@pytest.mark.parametrize("layers,b,kvh,g,hd,page,L,window,softcap",
                         STACKED_CASES)
def test_kernel_stacked_pool_matches_ref_per_layer(layers, b, kvh, g, hd,
                                                   page, L, window, softcap,
                                                   rng):
    """The kernel reads one layer of a pool stacked over layers, picked
    by its scalar-prefetch layer index, where it lies: every layer holds
    different values, and each index matches the oracle run on that
    layer's pool alone."""
    pos = jnp.asarray(_slot_positions(rng, b, page, L, kvh * hd), jnp.int32)
    b = pos.shape[0]
    n_lp = -(-L // page)
    n_pages = 2 + b * n_lp + 3
    shape = (layers, n_pages, page, kvh * hd)
    q = jnp.asarray(rng.standard_normal((b, kvh, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    block = jnp.asarray(
        rng.permutation(np.arange(2, n_pages))[:b * n_lp].reshape(b, n_lp),
        jnp.int32)
    kw = dict(cache_len=L, window=window, softcap=softcap)
    outs = []
    for layer in range(layers):
        ref = paged_attention(q, kp[layer], vp[layer], block, pos,
                              backend="ref", **kw)
        pal = paged_attention(q, kp, vp, block, pos,
                              jnp.asarray(layer, jnp.int32),
                              backend="pallas", **kw)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6,
                                   err_msg=f"layer {layer}")
        outs.append(np.asarray(pal))
    # the layers differ, so reading the wrong one could not pass above
    assert not np.allclose(outs[0], outs[1])


@pytest.mark.parametrize("kvh,g,hd,L,window", [
    (1, 1, 1024, 160, None),      # 8-page blocks of 10, ring wrap
    (2, 6, 128, 640, 72),         # 32-page blocks of 40, window
])
def test_kernel_ignores_poisoned_dead_rows(kvh, g, hd, L, window, rng,
                                           monkeypatch):
    """Every pool row that no valid row of any slot maps to holds NaN:
    the pages no live row maps to, and the invalid rows of live pages.
    The kernel fetches live pages only and masks V as well as the
    scores, so its output stays finite and matches the oracle on the
    clean pool.  It runs in TPU interpret mode, where a copy lands only
    when it is waited on and fresh VMEM holds NaN, so a stale or
    unfetched buffer row, or a page read before its wait, shows too."""
    monkeypatch.setattr(paged_kernel, "pallas_interpret",
                        lambda: pltpu.InterpretParams(
                            dma_execution_mode="on_wait",
                            uninitialized_memory="nan"))
    page = 16
    pos = _slot_positions(rng, 3, page, L, kvh * hd)
    b, n_lp = pos.shape[0], -(-L // page)
    n_pages = 2 + b * n_lp + 3
    q = jnp.asarray(rng.standard_normal((b, kvh, g, hd)), jnp.float32)
    kp = rng.standard_normal((n_pages, page, kvh * hd)).astype(np.float32)
    vp = rng.standard_normal((n_pages, page, kvh * hd)).astype(np.float32)
    block = rng.permutation(np.arange(2, n_pages))[:b * n_lp].reshape(b, n_lp)
    kw = dict(cache_len=L, window=window)
    ref = paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(block, jnp.int32),
                          jnp.asarray(pos, jnp.int32), backend="ref", **kw)
    # the oracle's rule: slot s holds pos - ((pos % L - s) % L)
    s = np.arange(n_lp * page)
    kv_pos = pos[:, None] - ((pos[:, None] % L - s[None]) % L)
    valid = (s[None] < L) & (kv_pos >= 0)
    if window is not None:
        valid &= kv_pos > pos[:, None] - window
    keep = np.zeros((n_pages, page), bool)
    keep[block.reshape(-1)] = valid.reshape(-1, page)
    assert 0 < keep.sum() < keep.size
    kp[~keep], vp[~keep] = np.nan, np.nan
    pal = paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(block, jnp.int32),
                          jnp.asarray(pos, jnp.int32), backend="pallas", **kw)
    assert np.isfinite(np.asarray(pal)).all()
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_kernel_rejects_bad_pool_rank_or_width(rng):
    q = jnp.zeros((1, 2, 1, 8), jnp.float32)
    block = jnp.full((1, 2), 2, jnp.int32)
    pos = jnp.zeros((1,), jnp.int32)
    one = jnp.zeros((4, 4, 16), jnp.float32)
    stacked = jnp.zeros((3, 4, 4, 16), jnp.float32)
    with pytest.raises(ValueError, match="layer index"):
        paged_attention(q, stacked, stacked, block, pos, cache_len=8)
    with pytest.raises(ValueError, match="single-layer"):
        paged_attention(q, one, one, block, pos, jnp.int32(0), cache_len=8)
    narrow = jnp.zeros((4, 4, 8), jnp.float32)   # one head's width
    with pytest.raises(ValueError, match="kv_heads"):
        paged_attention(q, narrow, narrow, block, pos, cache_len=8)


def test_kernel_rejects_short_block_table(rng):
    q = jnp.zeros((1, 1, 1, 8), jnp.float32)
    kp = jnp.zeros((4, 4, 8), jnp.float32)
    block = jnp.zeros((1, 2), jnp.int32)       # 2 pages x 4 rows < 12
    with pytest.raises(ValueError, match="block table"):
        paged_attention(q, kp, kp, block, jnp.zeros((1,), jnp.int32),
                        cache_len=12, backend="pallas")
    with pytest.raises(ValueError, match="backend"):
        paged_attention(q, kp, kp, block, jnp.zeros((1,), jnp.int32),
                        cache_len=8, backend="nope")


# ---------------------------------------------------------------------------
# model level: lockstep decode across backends, all archs
# ---------------------------------------------------------------------------
_CACHED = {}


def _arch(arch):
    """(model, params, jitted prefill, decode fns per backend, insert,
    per-backend PageTables) — cached per arch.  Each backend gets its
    OWN PageTable so its cache evolves through its own decode chain
    (separately jitted programs may fuse the K/V projection
    differently, so cross-program cache rows are close, not bitwise);
    the tables are driven with identical call sequences, so their page
    assignments are identical."""
    if arch not in _CACHED:
        cfg = get_config(arch, smoke=True)
        model = TransformerLM(cfg)
        params = model.init(jax.random.key(0))
        prefill = jax.jit(
            lambda p, t, n: model.prefill(p, t, MAX_CTX, lengths=n))
        tables = {be: PageTable(model, max_batch=2, max_ctx=MAX_CTX,
                                page_size=PAGE)
                  for be in ("gather", "pallas_paged")}
        decode = {
            be: jax.jit(functools.partial(model.decode_step,
                                          decode_backend=be))
            for be in ("gather", "pallas_paged")
        }
        _CACHED[arch] = (model, params, prefill, decode,
                         jax.jit(ServeEngine._insert_cache), tables)
    return _CACHED[arch]


def _build_pair(arch, plens):
    """Admit ``plens`` prompts into the contiguous cache and both
    backends' paged caches (slots 0..)."""
    model, params, prefill, decode, insert, tables = _arch(arch)
    cfg = model.cfg
    cache_c = model.init_cache(2, MAX_CTX)
    caches = {}
    for be, table in tables.items():
        table.reset()
        caches[be] = table.init_cache()
    toks = []
    for s, pl in enumerate(plens):
        row = np.random.default_rng(100 * pl + s).integers(
            0, cfg.vocab_size, (pl,)).astype(np.int32)
        padded = np.zeros((1, BUCKET), np.int32)
        padded[0, :pl] = row
        logits, one = prefill(params, jnp.asarray(padded),
                              jnp.asarray([pl], jnp.int32))
        cache_c = insert(cache_c, one, jnp.asarray(s, jnp.int32))
        for be, table in tables.items():
            caches[be] = table.admit(caches[be], one, s, pl)
        toks.append(int(jnp.argmax(logits[0])))
    return (model, params, decode, tables, cache_c, caches,
            np.asarray(toks, np.int32), np.asarray(plens, np.int32))


def _lockstep3(model, params, decode, tables, cache_c, caches,
               tok, pos, steps, msg):
    """Decode contiguous / paged-gather / paged-kernel in lockstep,
    each through its own cache chain.  Per step: gather logits ==
    contiguous logits bit-for-bit, kernel logits within TOL, and the
    kernel's greedy tokens IDENTICAL to the exact paths'.
    """
    tok_c = tok_g = tok_k = jnp.asarray(tok)
    for i in range(steps):
        for be, table in tables.items():
            for s in range(pos.shape[0]):
                caches[be], ok = table.prepare_step(
                    caches[be], s, int(pos[s]))
                assert ok, f"{msg}: {be} pool exhausted at step {i}"
            caches[be] = table.flush(caches[be])
        posj = jnp.asarray(pos)
        lc, cache_c = decode["gather"](params, cache_c, tok_c, posj)
        lg, caches["gather"] = decode["gather"](
            params, caches["gather"], tok_g, posj)
        lk, caches["pallas_paged"] = decode["pallas_paged"](
            params, caches["pallas_paged"], tok_k, posj)
        np.testing.assert_array_equal(
            np.asarray(lc), np.asarray(lg),
            err_msg=f"{msg}: step {i} gather != contiguous")
        np.testing.assert_allclose(
            np.asarray(lk), np.asarray(lg), atol=TOL, rtol=TOL,
            err_msg=f"{msg}: step {i} kernel logits")
        tok_c = jnp.argmax(lc, -1).astype(jnp.int32)
        tok_g = jnp.argmax(lg, -1).astype(jnp.int32)
        tok_k = jnp.argmax(lk, -1).astype(jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(tok_k), np.asarray(tok_g),
            err_msg=f"{msg}: step {i} kernel tokens diverged")
        pos = pos + 1
    return cache_c, caches, tok_g, pos


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_kernel_decode_all_archs(arch):
    """decode_backend='pallas_paged' tracks gather and contiguous
    decode on every arch: tokens identical, logits within TOL,
    through page growth past the prefill lengths."""
    plens = (7, 10)
    (model, params, decode, tables, cache_c, caches,
     tok, pos) = _build_pair(arch, plens)
    steps = min(8, MAX_CTX - max(plens))
    _lockstep3(model, params, decode, tables, cache_c, caches,
               tok, pos, steps, arch)


def test_kernel_decode_survives_offload_resume():
    """Post-preemption resume: offload a slot's pages to host, restore
    into different physical pages, and keep decoding through the
    kernel — tokens still match the exact paths."""
    (model, params, decode, tables, cache_c, caches,
     tok, pos) = _build_pair("qwen1.5-0.5b", (7, 10))
    cache_c, caches, tok, pos = _lockstep3(
        model, params, decode, tables, cache_c, caches,
        tok, pos, 3, "pre-offload")
    for be, table in tables.items():
        caches[be], payload = table.offload(caches[be], 1, int(pos[1]))
        caches[be] = table.restore(caches[be], 1, payload)
    _lockstep3(model, params, decode, tables, cache_c, caches,
               tok, pos, 3, "post-restore")


def test_pallas_backend_requires_paged_cache():
    model, params, *_ = _arch("qwen1.5-0.5b")
    cache = model.init_cache(1, 8)
    step = functools.partial(model.decode_step,
                             decode_backend="pallas_paged")
    with pytest.raises(ValueError, match="pallas_paged"):
        step(params, cache, jnp.zeros((1,), jnp.int32),
             jnp.zeros((1,), jnp.int32))
    with pytest.raises(ValueError, match="decode backend"):
        model.decode_step(params, cache, jnp.zeros((1,), jnp.int32),
                          jnp.zeros((1,), jnp.int32),
                          decode_backend="typo")


# ---------------------------------------------------------------------------
# engine level: all archs, generations identical (acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.slow_serve
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_engine_kernel_backend_matches_gather_all_archs(arch):
    """ServeEngine(decode_backend='pallas_paged') serves a mixed
    greedy+stochastic workload with generations identical to the
    gather engine — growth past the prefill cap included."""
    cfg = get_config(arch, smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    kw = dict(max_len=16, max_batch=2,
              paged=PagedCacheConfig(page_size=PAGE, max_ctx=32))
    gather = ServeEngine(model, params, **kw)
    kernel = ServeEngine(model, params, decode_backend="pallas_paged", **kw)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 9, 3)]
    temps, topks = [0.0, 50.0, 50.0], [None, None, 5]
    a = gather.serve(prompts, 18, temperature=temps, top_k=topks, seed=11)
    b = kernel.serve(prompts, 18, temperature=temps, top_k=topks, seed=11)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(x, y, err_msg=f"{arch} request {i}")


def test_engine_kernel_backend_preemption_resume():
    """A tight resident-page budget forces offload mid-serve on the
    kernel backend; generations still match the gather engine."""
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    kw = dict(max_len=16, max_batch=2,
              paged=PagedCacheConfig(page_size=8, max_ctx=32,
                                     resident_pages=6))
    gather = ServeEngine(model, params, **kw)
    kernel = ServeEngine(model, params, decode_backend="pallas_paged", **kw)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (6, 9, 4)]
    tg, tk = [ServeTelemetry(TrafficModel.from_config(
        get_config("qwen1.5-0.5b"), max_len=4096, page_size=8))
        for _ in range(2)]
    a = gather.serve(prompts, 20, seed=5, telemetry=tg)
    b = kernel.serve(prompts, 20, seed=5, telemetry=tk)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(x, y, err_msg=f"request {i}")
    assert tk.page_outs > 0 and tk.page_ins > 0   # preemption happened


def test_engine_rejects_kernel_backend_without_paging():
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = TransformerLM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    with pytest.raises(ValueError, match="pallas_paged"):
        ServeEngine(model, params, max_len=16, max_batch=2,
                    decode_backend="pallas_paged")
    with pytest.raises(ValueError, match="decode_backend"):
        ServeEngine(model, params, max_len=16, max_batch=2,
                    decode_backend="vulkan")


# ---------------------------------------------------------------------------
# telemetry: kernel path accounts per-page bytes only
# ---------------------------------------------------------------------------
def test_kernel_telemetry_per_page_reads_only():
    """Acceptance: on the kernel path the RTC profile sees true
    per-page reads — zero materialized-view traffic — while the gather
    path pays the phantom whole-view copy every step."""
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    kw = dict(max_len=16, max_batch=2,
              paged=PagedCacheConfig(page_size=4, max_ctx=32))
    t = TrafficModel.from_config(get_config("qwen1.5-0.5b"), max_len=4096,
                                 page_size=4)
    tg, tk = ServeTelemetry(t), ServeTelemetry(t)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 8)]
    ServeEngine(model, params, **kw).serve(prompts, 8, telemetry=tg)
    ServeEngine(model, params, decode_backend="pallas_paged", **kw) \
        .serve(prompts, 8, telemetry=tk)

    assert tg.decode_mode == "gather" and tk.decode_mode == "pallas_paged"
    # same schedule, so per-step shapes line up
    assert tg.decode_steps == tk.decode_steps
    # kernel path: no phantom traffic, page-granular KV reads
    assert tk.gather_read_bytes_total == 0
    assert tk.gather_write_bytes_total == 0
    assert tg.gather_read_bytes_total > 0
    assert tg.gather_write_bytes_total > 0
    # page-rounding reads at least the row-exact sweep, and the gather
    # path's total (sweep + phantom) strictly dominates the kernel's
    assert tk.kv_read_bytes_total >= tg.kv_read_bytes_total
    wg = tg.workload_profile(step_period_s=0.01)
    wk = tk.workload_profile(step_period_s=0.01)
    assert wg.read_bytes_per_iter > wk.read_bytes_per_iter
    assert wg.write_bytes_per_iter > wk.write_bytes_per_iter
    # per-page reads are exact: reconstruct from the traffic model
    assert t.kv_page_read_bytes(5) == sum(
        (-(-min(5, c) // 4) * 4) * b
        for c, b in zip(t.kv_caps, t.kv_token_bytes))


def test_explicit_decode_mode_is_pinned():
    """A mode passed to the constructor survives engine configuration
    (and bad modes are rejected eagerly)."""
    t = TrafficModel.from_config(get_config("qwen1.5-0.5b"), max_len=64)
    tele = ServeTelemetry(t, decode_mode="contiguous")
    tele.configure_decode(backend="gather", paged=True)
    assert tele.decode_mode == "contiguous"
    auto = ServeTelemetry(t)
    auto.configure_decode(backend="gather", paged=True)
    assert auto.decode_mode == "gather"
    auto.configure_decode(backend="gather", paged=False)
    assert auto.decode_mode == "contiguous"
    with pytest.raises(ValueError, match="decode_mode"):
        ServeTelemetry(t, decode_mode="magic")
