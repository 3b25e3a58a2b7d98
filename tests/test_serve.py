"""Serving engine (prefill / continuous batching / sampling / telemetry)
+ optimizer units."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.dram import module
from repro.core.rtc import Variant, evaluate
from repro.models.transformer import TransformerLM
from repro.serve import (PagedCacheConfig, ServeEngine, ServeTelemetry,
                         TrafficModel)
from repro.train.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                   cosine_schedule, global_norm)

# randomly-initialized smoke models have near-degenerate logits (one
# dominant token); this temperature flattens them enough to exercise
# the stochastic path
HOT = 50.0


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def engine(qwen):
    _, model, params = qwen
    return ServeEngine(model, params, max_len=32, max_batch=3)


@pytest.fixture(scope="module")
def solo_engine(qwen):
    """Same model, one batch slot: the per-sequence reference."""
    _, model, params = qwen
    return ServeEngine(model, params, max_len=32, max_batch=1)


@pytest.fixture(scope="module")
def mixed_prompts(qwen):
    cfg = qwen[0]
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (5, 9, 3, 12, 7)]


# ---------------------------------------------------------------------------
# one-shot prefill
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "mixtral-8x22b"])
def test_prefill_matches_decode_sweep(arch):
    """model.prefill (ONE full-sequence forward) must agree with the
    token-by-token decode path — logits and the continued generation.
    Covers the ring/append KV caches, recurrent (conv/ssm/rglru) state
    hand-off, and dropless MoE prefill dispatch."""
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype="float32")
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(0))
    toks = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 7)).astype(np.int32)

    logits_p, cache_p = jax.jit(
        lambda p, t: model.prefill(p, t, 24))(params, jnp.asarray(toks))
    dec = jax.jit(model.decode_step)
    cache_d = model.init_cache(2, 24)
    for t in range(7):
        logits_d, cache_d = dec(params, cache_d,
                                jnp.asarray(toks[:, t]), jnp.asarray(t))
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_d),
                               rtol=1e-4, atol=1e-4)
    tok_p = jnp.argmax(logits_p, -1).astype(jnp.int32)
    tok_d = jnp.argmax(logits_d, -1).astype(jnp.int32)
    for i in range(3):   # caches must be interchangeable going forward
        lp, cache_p = dec(params, cache_p, tok_p, jnp.asarray(7 + i))
        ld, cache_d = dec(params, cache_d, tok_d, jnp.asarray(7 + i))
        tok_p = jnp.argmax(lp, -1).astype(jnp.int32)
        tok_d = jnp.argmax(ld, -1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(tok_p), np.asarray(tok_d))


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------
def test_continuous_batching_matches_per_sequence(engine, solo_engine,
                                                  mixed_prompts):
    """5 mixed-length requests over 3 slots (forcing mid-flight
    admit/retire) must produce exactly the tokens each request gets
    when served alone."""
    batched = engine.serve(mixed_prompts, 6)
    for i, p in enumerate(mixed_prompts):
        alone = solo_engine.serve([p], 6)[0]
        np.testing.assert_array_equal(batched[i], alone)


def test_continuous_batching_temperature_schedule_independent(
        engine, solo_engine, mixed_prompts):
    """Sampling keys are (request, token-index)-addressed, so even the
    stochastic path is independent of slot scheduling."""
    batched = engine.serve(mixed_prompts, 6, temperature=HOT, seed=11)
    sequential = solo_engine.serve(mixed_prompts, 6, temperature=HOT, seed=11)
    for a, b in zip(batched, sequential):
        np.testing.assert_array_equal(a, b)


def test_step_api_matches_serve(engine, mixed_prompts):
    """new_cache / prefill_into / decode_step — the admission and step
    path serve itself runs — reproduce serve's first two greedy tokens."""
    prompts = mixed_prompts[:engine.max_batch]
    want = engine.serve(prompts, 2)
    cache = engine.new_cache()
    tok = np.zeros((engine.max_batch,), np.int32)
    pos = np.zeros((engine.max_batch,), np.int32)
    for s, p in enumerate(prompts):
        logits, cache, _ = engine.prefill_into(cache, s, p)
        tok[s], pos[s] = int(np.argmax(np.asarray(logits[0]))), p.size
    logits, _ = engine.decode_step(cache, tok, pos)
    nxt = np.argmax(np.asarray(logits), -1)
    for s, w in enumerate(want):
        np.testing.assert_array_equal([tok[s], nxt[s]], w)


def test_eos_retirement_frees_slot(engine, solo_engine, mixed_prompts):
    """Retiring on EOS mid-flight must not disturb other requests."""
    ref = engine.serve(mixed_prompts, 6)
    eos = int(ref[0][1])   # second token of request 0 becomes "EOS"
    outs = engine.serve(mixed_prompts, 6, eos_id=eos)
    for got, full in zip(outs, ref):
        stop = np.where(full == eos)[0]
        want = full[:stop[0] + 1] if stop.size else full
        np.testing.assert_array_equal(got, want)
    padded = engine.generate(
        np.stack([p[:3] for p in mixed_prompts[:2]]), 6, eos_id=eos)
    assert padded.shape == (2, 6)


@pytest.mark.parametrize("new_tokens", [1, 4])
def test_first_token_retirement_refills_slots(engine, solo_engine,
                                              mixed_prompts, new_tokens):
    """A request that ends on its first token (one new token, or EOS
    first) frees its slot once the pass's first tokens land; the next
    pass admits into it, and every request keeps its solo tokens."""
    ref = [solo_engine.serve([p], new_tokens)[0] for p in mixed_prompts]
    eos = int(ref[1][0])      # request 1's first token becomes "EOS"
    outs = engine.serve(mixed_prompts, new_tokens, eos_id=eos)
    for got, full in zip(outs, ref):
        stop = np.where(full == eos)[0]
        want = full[:stop[0] + 1] if stop.size else full
        np.testing.assert_array_equal(got, want)
    assert len(outs[1]) == 1


def test_one_first_token_pull_per_admission_pass(engine, mixed_prompts,
                                                 monkeypatch):
    """The first tokens of one admission pass come to the host in one
    transfer: 5 requests over 3 slots, 4 new tokens each, admit 3 at
    the start and 2 in one later pass (both slots free at one step)."""
    import repro.serve.engine as engine_mod
    pulls = []
    real = engine_mod.jax.device_get

    def counted(x):
        if isinstance(x, list):
            pulls.append(len(x))
        return real(x)
    monkeypatch.setattr(engine_mod.jax, "device_get", counted)
    prompts = [p[:5] for p in mixed_prompts]
    engine.serve(prompts, 4)
    assert pulls == [3, 2]


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------
def test_serve_engine_greedy_deterministic(engine, mixed_prompts):
    prompts = np.stack([p[:3] for p in mixed_prompts[:3]])
    a = engine.generate(prompts, 8, temperature=0.0)
    b = engine.generate(prompts, 8, temperature=0.0)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (3, 8)
    vocab = engine.model.cfg.vocab_size
    assert (a >= 0).all() and (a < vocab).all()


def test_serve_engine_sampling_deterministic_by_seed(engine, mixed_prompts):
    a = engine.serve(mixed_prompts, 8, temperature=HOT, seed=1)
    b = engine.serve(mixed_prompts, 8, temperature=HOT, seed=2)
    c = engine.serve(mixed_prompts, 8, temperature=HOT, seed=1)
    assert any(not np.array_equal(x, y) for x, y in zip(a, b))
    for x, y in zip(a, c):
        np.testing.assert_array_equal(x, y)


def test_first_token_respects_temperature(engine, mixed_prompts):
    """Seed-engine bug regression: the first emitted token used to be
    argmaxed unconditionally; it must go through the same sampler."""
    firsts = {
        int(engine.serve(mixed_prompts[:1], 1,
                         temperature=HOT, seed=s)[0][0])
        for s in range(8)
    }
    assert len(firsts) > 1


def test_per_request_sampling_params(engine, solo_engine, mixed_prompts):
    """temperature/top_k live on each request: a mixed greedy+temperature
    batch reproduces each request's solo generation bit-for-bit."""
    temps = [0.0, HOT, HOT, 0.0, HOT]
    topks = [None, None, 5, 3, None]
    mixed = engine.serve(mixed_prompts, 6, temperature=temps, top_k=topks,
                         seed=11)
    sequential = solo_engine.serve(mixed_prompts, 6, temperature=temps,
                                   top_k=topks, seed=11)
    for i, (a, b) in enumerate(zip(mixed, sequential)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    # greedy requests are key-independent -> comparable to a true solo
    # serve (request ids restart at 0, but greedy never draws a key)
    np.testing.assert_array_equal(
        mixed[0], solo_engine.serve([mixed_prompts[0]], 6)[0])
    # each request's params are isolated: request 1 matches the same
    # request position under an all-HOT call, request 3 (temp 0) matches
    # pure greedy serving regardless of its top_k
    hot_all = engine.serve(mixed_prompts, 6, temperature=HOT, seed=11)
    np.testing.assert_array_equal(mixed[1], hot_all[1])
    greedy_all = engine.serve(mixed_prompts, 6)
    np.testing.assert_array_equal(mixed[3], greedy_all[3])


def test_per_request_param_validation(engine, mixed_prompts):
    with pytest.raises(ValueError, match="temperature"):
        engine.serve(mixed_prompts[:2], 2, temperature=[0.0])
    with pytest.raises(ValueError, match="top_k"):
        engine.serve(mixed_prompts[:2], 2, top_k=[2, 0])


def test_temperature_rejected_like_top_k(engine, mixed_prompts):
    """A negative temperature flips the softmax ordering and NaN poisons
    every draw — both must be rejected up front with the offending
    request index named, symmetric with the ``top_k >= 1`` check, in
    both the scalar and per-request forms."""
    with pytest.raises(ValueError, match=r"temperature.*\(request 0\)"):
        engine.serve(mixed_prompts[:2], 2, temperature=-1.0)
    with pytest.raises(ValueError, match=r"temperature.*\(request 1\)"):
        engine.serve(mixed_prompts[:2], 2, temperature=[0.5, float("nan")])
    with pytest.raises(ValueError, match=r"temperature.*\(request 1\)"):
        engine.serve(mixed_prompts[:2], 2, temperature=[0.5, -0.25])
    with pytest.raises(ValueError, match=r"top_k.*\(request 1\)"):
        engine.serve(mixed_prompts[:2], 2, top_k=[2, 0])
    # zero stays valid: it IS greedy decoding
    out = engine.serve(mixed_prompts[:1], 1, temperature=0.0)
    assert out[0].shape == (1,)


def test_top_k_one_is_greedy(engine, mixed_prompts):
    hot = engine.serve(mixed_prompts[:2], 6, temperature=HOT, top_k=1, seed=5)
    greedy = engine.serve(mixed_prompts[:2], 6)
    for a, b in zip(hot, greedy):
        np.testing.assert_array_equal(a, b)


def test_empty_prompt_validation(qwen, engine):
    with pytest.raises(ValueError, match="empty prompt"):
        engine.serve([np.zeros((0,), np.int32)], 4)
    _, model, params = qwen
    bos_engine = ServeEngine(model, params, max_len=16, max_batch=1, bos_id=1)
    out = bos_engine.serve([np.zeros((0,), np.int32)], 4)[0]
    assert out.shape == (4,)
    with pytest.raises(ValueError, match="max_len"):
        engine.serve([np.zeros((33,), np.int32)], 4)


def test_oversized_prompt_names_request_and_lengths(engine, mixed_prompts):
    """An over-long prompt must be rejected UP FRONT with the offending
    request index and both lengths in the message — not fail opaquely
    inside PrefillBuckets.bucket_for mid-serve, after other requests
    already ran."""
    bad = np.zeros((40,), np.int32)          # engine max_len is 32
    hits_before = dict(engine.buckets.hits)
    with pytest.raises(ValueError,
                       match=r"prompt 2 has length 40.*bucket 32"):
        engine.serve([mixed_prompts[0], mixed_prompts[1], bad], 4)
    # validation ran before any prefill: nothing was served or recorded
    assert engine.buckets.hits == hits_before
    # the index is the caller's position, also for empty prompts
    with pytest.raises(ValueError, match="index 1"):
        engine.serve([mixed_prompts[0], np.zeros((0,), np.int32)], 4)


# ---------------------------------------------------------------------------
# telemetry -> WorkloadProfile -> RTC
# ---------------------------------------------------------------------------
def test_telemetry_workload_profile(engine, mixed_prompts):
    """Serving traffic must flow into the paper's energy model: the
    engine-emitted profile is a sane decode-phase WorkloadProfile that
    rtc.evaluate accepts."""
    full = get_config("qwen1.5-0.5b")
    traffic = TrafficModel.from_config(full, max_len=4096)
    tele = ServeTelemetry(traffic)
    engine.serve(mixed_prompts, 6, telemetry=tele)

    assert tele.n_prefills == len(mixed_prompts)
    assert tele.prefill_tokens == sum(p.shape[0] for p in mixed_prompts)
    assert tele.tokens_generated == 6 * len(mixed_prompts)
    assert 1 <= tele.max_live <= engine.max_batch

    w = tele.workload_profile(name="qwen/serve", step_period_s=0.01)
    assert w.regular
    assert w.read_bytes_per_iter > traffic.param_read_bytes  # weights + KV
    assert w.write_bytes_per_iter > 0
    assert w.footprint_bytes == traffic.param_bytes \
        + tele.max_live * traffic.cache_slot_bytes

    spec = module(4)
    rep = evaluate(spec, w, Variant.FULL_RTC_PLUS)
    assert 0.0 < rep.refresh_savings <= 1.0


class _MinimalSink:
    """Three hooks, as the benchmark's timestamp sink has: every other
    hook the serve loop calls (``record_page_in``, ``record_cow``, ...)
    and ``trace`` are missing."""

    def __init__(self):
        self.prefill, self.decode, self.page_out = [], [], []

    def record_prefill(self, plen, dt=0.0, padded_len=None):
        self.prefill.append(plen)

    def record_decode(self, ctx_lengths, dt=0.0):
        self.decode.append(list(ctx_lengths))

    def record_page_out(self, ctx):
        self.page_out.append(ctx)


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_minimal_sink_serves(qwen, mixed_prompts, paged):
    """A sink with some of the hooks serves the tokens a serve without
    telemetry does, and its hooks fire as often as
    :class:`ServeTelemetry`'s.  The paged engine's budget of 8 pages
    preempts, so the missing ``record_page_in`` is called too."""
    cfg, model, params = qwen
    kw = {"paged": PagedCacheConfig(page_size=8, resident_pages=8)} \
        if paged else {}
    eng = ServeEngine(model, params, max_len=32, max_batch=3, **kw)
    want = eng.serve(mixed_prompts, 20)
    sink = _MinimalSink()
    got = eng.serve(mixed_prompts, 20, telemetry=sink)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    full = ServeTelemetry(TrafficModel.from_config(cfg, max_len=32))
    eng.serve(mixed_prompts, 20, telemetry=full)
    assert sink.prefill == [p.shape[0] for p in mixed_prompts]
    assert len(sink.decode) == full.decode_steps
    assert len(sink.prefill) + sum(map(len, sink.decode)) == sum(
        o.shape[0] for o in got)
    assert len(sink.page_out) == full.page_outs == full.page_ins
    assert (full.page_outs > 0) == paged


def test_traffic_model_accounting():
    """Byte constants follow directly from the config geometry."""
    cfg = get_config("gemma2-9b")       # (local, global) pattern
    t = TrafficModel.from_config(cfg, max_len=8192)
    itemsize = 2
    per_layer = 2 * cfg.n_kv_heads * cfg.resolved_head_dim * itemsize
    assert t.kv_token_bytes == (per_layer,) * cfg.n_layers
    n_local = sum(cfg.layer_kind(i) == "local" for i in range(cfg.n_layers))
    assert sorted(set(t.kv_caps)) == sorted({8192, cfg.window_size})
    assert t.kv_caps.count(cfg.window_size) == n_local
    # reads are capped by each layer's cache length
    assert t.kv_read_bytes(10**9) == t.cache_slot_bytes - t.state_bytes
    assert t.kv_read_bytes(1) == cfg.n_layers * per_layer
    assert t.param_bytes == cfg.param_counts()["total"] * itemsize


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_reduces_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=1000, min_lr_ratio=1.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 1e-2
    assert int(state.step) == 200


def test_grad_clipping_bounds_update():
    cfg = AdamWConfig(lr=1.0, clip_norm=1e-3, weight_decay=0.0,
                      warmup_steps=0)
    params = {"w": jnp.zeros((4,))}
    state = adamw_init(params)
    huge = {"w": jnp.full((4,), 1e9)}
    new, state = adamw_update(cfg, params, huge, state)
    # clipped grad -> bounded first step
    assert float(jnp.abs(new["w"]).max()) < 10.0


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lr = cosine_schedule(cfg)
    assert float(lr(jnp.asarray(0))) < float(lr(jnp.asarray(9)))
    assert float(lr(jnp.asarray(10))) == pytest.approx(1.0, abs=0.02)
    assert float(lr(jnp.asarray(99))) == pytest.approx(0.1, abs=0.05)


def test_global_norm():
    t = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    assert float(global_norm(t)) == pytest.approx(5.0)
