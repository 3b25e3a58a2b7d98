"""Smoke run of the serving main path on a TPU.

    python chip_smoke.py [--seed 0]      # one chip
    python chip_smoke.py --chips 4       # shard_map decode on four chips

One chip: ``qwen1.5-0.5b`` at its published width (24 layers, d_model
1024, vocab 151936, bf16; random weights from ``--seed``) is served by
``ServeEngine`` through the paged cache with the compiled Pallas
paged-decode kernel (``decode_backend="pallas_paged"``).  Phases:

* ``serve``        — 16 requests, prompts spread over 16-512 tokens, 32
                     new tokens each; every token in the vocabulary, and
                     a second (warm) serve reproduces the first.
* ``kernel_vs_ref`` — on the engine's own prefilled cache, the Pallas
                     kernel matches the op's jnp reference within
                     ``KERNEL_TOL``.
* ``first_decode`` — the first decode step's logits of the kernel engine
                     match a ``gather`` engine's on the same prompts
                     within ``LOGIT_TOL``.
* ``offload``      — a resident-page budget at the slot floor forces
                     offload to host and restore; generations match the
                     unforced serve.

``--chips 4`` runs only the device-local ``shard_map`` decode on a
(data=4, model=1) mesh (device-local pools: ``page_table.shards == 4``)
and the one-chip engine it is compared with, in this one process.

Times printed here are smoke figures of one run, not benchmark
metrics.  Every check raises on failure, so the script exits non-zero
without its last line, which is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Without a TPU it exits non-zero before building anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "qwen1.5-0.5b"
#: kernel vs reference: max |pallas - ref| over max |ref| (bf16 outputs;
#: the two differ by accumulation order and one bf16 rounding)
KERNEL_TOL = 2e-2
#: first-decode logits, kernel engine vs gather engine (or four chips vs
#: one): max |a - b| over max |b|, after 24 bf16 layers whose attention
#: outputs differ by accumulation order
LOGIT_TOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Spec:
    """Sizes of the smoke run (defaults: the one-chip run)."""

    max_len: int = 1024
    page_size: int = 16
    max_batch: int = 8
    n_requests: int = 16
    prompt_min: int = 16
    prompt_max: int = 512
    new_tokens: int = 32


class CompileClock:
    """Seconds the backend spends compiling (and how many programs came
    from the persistent cache), from JAX's own events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def _rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    _check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
           "non-finite values")
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def prompts_for(spec: Spec, vocab: int, seed: int):
    """``n_requests`` seeded prompts, lengths spread evenly over
    [prompt_min, prompt_max]."""
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = np.linspace(spec.prompt_min, spec.prompt_max,
                       spec.n_requests).round().astype(int)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lens]


def make_engine(model, params, spec: Spec, backend="pallas_paged",
                resident_pages=None, mesh=None):
    from repro.serve import PagedCacheConfig, ServeEngine
    return ServeEngine(
        model, params, max_len=spec.max_len, max_batch=spec.max_batch,
        mesh=mesh, decode_backend=backend,
        paged=PagedCacheConfig(page_size=spec.page_size,
                               resident_pages=resident_pages))


def admit(engine, prompts):
    """Prefill ``prompts`` into slots 0.. through the engine's own
    admission path and assign the pages the first decode step writes.
    Returns (cache, first tokens, positions)."""
    import jax.numpy as jnp
    import numpy as np
    cache = engine.new_cache()
    tok = np.zeros((engine.max_batch,), np.int32)
    pos = np.zeros((engine.max_batch,), np.int32)
    for s, p in enumerate(prompts):
        logits, cache, _ = engine.prefill_into(cache, s, p)
        tok[s], pos[s] = int(jnp.argmax(logits[0])), p.size
    for s in range(len(prompts)):
        cache, ok = engine.page_table.prepare_step(cache, s, int(pos[s]))
        _check(ok, f"slot {s}: no page for the first decode write")
    return cache, tok, pos


def first_decode(engine, prompts):
    """Logits [n, vocab] of the first decode step after prefill."""
    import numpy as np
    cache, tok, pos = admit(engine, prompts)
    logits, _ = engine.decode_step(cache, tok, pos)
    return np.asarray(logits.block_until_ready())[:len(prompts)]


def timed_serve(engine, prompts, spec: Spec, **kw):
    """(outputs, wall seconds); outputs are host numpy, so the clock
    stops after the device is done."""
    t0 = time.perf_counter()
    outs = engine.serve(prompts, spec.new_tokens, **kw)
    return outs, time.perf_counter() - t0


def check_generations(outs, spec: Spec, vocab: int) -> None:
    for i, o in enumerate(outs):
        _check(o.shape == (spec.new_tokens,),
               f"request {i}: {o.shape[0]} tokens, want {spec.new_tokens}")
        _check(bool(((o >= 0) & (o < vocab)).all()),
               f"request {i}: token outside the vocabulary")


def agreement(a, b) -> float:
    import numpy as np
    return float(np.mean(np.concatenate(a) == np.concatenate(b)))


def phase_serve(model, params, spec, prompts, clock):
    engine = make_engine(model, params, spec)
    vocab = model.cfg.vocab_size
    c0 = clock.seconds
    cold, t_cold = timed_serve(engine, prompts, spec)
    compile_s = clock.seconds - c0
    check_generations(cold, spec, vocab)
    warm, t_warm = timed_serve(engine, prompts, spec)
    _check(agreement(cold, warm) == 1.0, "warm serve differs from cold")
    n_tok = sum(o.size for o in warm)
    print(f"phase serve: PASS  {len(prompts)} requests, {n_tok} tokens; "
          f"smoke figures (not benchmark metrics): cold serve "
          f"{t_cold:.3f} s incl. {compile_s:.3f} s compile, warm serve "
          f"{t_warm:.3f} s = {n_tok / t_warm:.1f} tok/s", flush=True)
    return engine, warm


def phase_kernel_vs_ref(engine, prompts, seed):
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention.ops import paged_attention
    cfg = engine.model.cfg
    cache, _, pos = admit(engine, prompts)
    node = cache["groups"][0]                     # stacked over layers
    kp, vp, block = node.kp, node.vp, node.block[-1]
    last = jnp.asarray(kp.shape[0] - 1, jnp.int32)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    q = jax.random.normal(jax.random.key(seed),
                          (engine.max_batch, kvh, cfg.n_heads // kvh, hd),
                          kp.dtype)
    # query the last prompt position: every slot's row is written
    qpos = jnp.asarray(jnp.maximum(pos - 1, 0), jnp.int32)
    kw = dict(cache_len=node.cache_len)
    pal = paged_attention(q, kp, vp, block, qpos, last, backend="pallas",
                          **kw)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention(q, kp, vp, block, qpos, last, backend="ref",
                              **kw)
    n = len(prompts)
    err = _rel_err(pal.block_until_ready()[:n], ref.block_until_ready()[:n])
    _check(err <= KERNEL_TOL, f"kernel vs ref: {err:.3e} > {KERNEL_TOL}")
    print(f"phase kernel_vs_ref: PASS  last layer, pools "
          f"{tuple(kp.shape)} {kp.dtype}: max rel err {err:.3e} "
          f"<= {KERNEL_TOL}", flush=True)


def compare_first_decode(name, a, b):
    import numpy as np
    err = _rel_err(a, b)
    agree = float(np.mean(a.argmax(-1) == b.argmax(-1)))
    print(f"{name}: first-decode argmax agreement {agree:.3f} "
          f"({a.shape[0]} slots), max rel logit err {err:.3e}", flush=True)
    _check(err <= LOGIT_TOL, f"{name}: {err:.3e} > {LOGIT_TOL}")
    return err


def phase_first_decode(model, params, spec, engine, prompts):
    gather = make_engine(model, params, spec, backend="gather")
    a = first_decode(engine, prompts)
    b = first_decode(gather, prompts)
    err = compare_first_decode("pallas_paged vs gather", a, b)
    print(f"phase first_decode: PASS  {err:.3e} <= {LOGIT_TOL}", flush=True)


def phase_offload(model, params, spec, prompts, reference):
    from repro.serve import ServeTelemetry, TrafficModel
    from repro.serve.paging import slot_floor
    floor = slot_floor(model.cfg, spec.max_len, spec.page_size)
    engine = make_engine(model, params, spec, resident_pages=floor)
    tele = ServeTelemetry(TrafficModel.from_config(
        model.cfg, max_len=spec.max_len, page_size=spec.page_size))
    outs, wall = timed_serve(engine, prompts, spec, telemetry=tele)
    agree = agreement(outs, reference)
    print(f"offload: {floor} resident pages, {tele.page_outs} page-outs, "
          f"{tele.page_ins} page-ins, token agreement with the unforced "
          f"serve {agree:.3f}; smoke wall {wall:.3f} s", flush=True)
    _check(tele.page_outs > 0 and tele.page_ins > 0,
           "the tight budget forced no offload/restore")
    _check(agree == 1.0, "offloaded serve differs from the unforced serve")
    print("phase offload: PASS", flush=True)


def run_one_chip(model, params, spec, seed, clock):
    prompts = prompts_for(spec, model.cfg.vocab_size, seed)
    engine, outs = phase_serve(model, params, spec, prompts, clock)
    batch = prompts[:spec.max_batch]
    phase_kernel_vs_ref(engine, batch, seed)
    phase_first_decode(model, params, spec, engine, batch)
    phase_offload(model, params, spec, prompts, outs)


def run_four_chips(model, params, spec, seed, devices):
    import numpy as np
    from jax.sharding import Mesh
    prompts = prompts_for(spec, model.cfg.vocab_size, seed)
    mesh = Mesh(np.array(devices[:4]).reshape(4, 1), ("data", "model"))
    meshed = make_engine(model, params, spec, mesh=mesh)
    shards = meshed.page_table.shards
    print(f"shard_map engine: mesh (data=4, model=1), page_table.shards="
          f"{shards}", flush=True)
    _check(shards == 4, f"pools are not device-local: shards={shards}")
    solo = make_engine(model, params, spec)
    batch = prompts[:spec.max_batch]
    compare_first_decode("4 chips vs 1", first_decode(meshed, batch),
                         first_decode(solo, batch))
    a, t_a = timed_serve(meshed, prompts, spec)
    b, t_b = timed_serve(solo, prompts, spec)
    check_generations(a, spec, model.cfg.vocab_size)
    print(f"4 chips vs 1: served token agreement {agreement(a, b):.3f}; "
          f"smoke walls (cold, not benchmark metrics) {t_a:.3f} s on 4, "
          f"{t_b:.3f} s on 1", flush=True)
    print("phase shard_map: PASS", flush=True)


def build_model(seed):
    import jax
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM
    cfg = get_config(ARCH)
    _check((cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.dtype)
           == (24, 1024, 151936, "bfloat16"),
           f"{ARCH} is not at its published width: {cfg}")
    model = TransformerLM(cfg)
    params = model.init(jax.random.key(seed))
    jax.block_until_ready(params)
    return model, params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    d0 = devices[0]
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"device_count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        print("no TPU: this smoke run has no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    model, params = build_model(args.seed)
    print(f"model {ARCH}: {model.cfg.n_layers} layers, d_model "
          f"{model.cfg.d_model}, vocab {model.cfg.vocab_size}, "
          f"{model.cfg.dtype}, seed {args.seed}", flush=True)
    spec = Spec()
    if args.chips == 4:
        run_four_chips(model, params, spec, args.seed, devices)
    else:
        run_one_chip(model, params, spec, args.seed, clock)
    print(f"smoke totals (not benchmark metrics): wall "
          f"{time.perf_counter() - t0:.3f} s, compile {clock.seconds:.3f} s, "
          f"{clock.cache_hits} persistent-cache hits", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
