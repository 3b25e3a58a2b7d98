"""One run of one cell: set-up, the measured window, the check, the line.

Set-up (``setup_s``, from process start to the window's first request):
backend start, weights made on the device in one jitted call, the
engine, and one warm-up ``serve`` that runs every prefill bucket the
cell's prompt lengths reach, plus decode, sampling and the page table.
Compiled programs come from JAX's persistent cache in the checkout.

The window: the traffic's jobs, one ``serve()`` call each, back to back,
with the timestamp sink (:mod:`bench.sink`) as ``telemetry``; the sink
ends the job in flight at the deadline.  Compilations inside the window
are counted.  With ``--trace 1`` the window runs under the profiler,
with host spans around the engine's calls, and the run reports the
per-layer metrics; otherwise it reports the end-to-end ones.

Then the program's state is freed and the check of
:mod:`bench.check` runs on the requests the window finished.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from bench import check, sink as sink_mod, system
from bench.spec import Bench
from bench.weights import Dims, dims_of

__all__ = ["CompileClock", "Run", "run_cell"]


class CompileClock:
    """Seconds the backend spends compiling, programs compiled, and
    programs loaded from the persistent cache, from JAX's own events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class Run:
    """What a metric's reader gets."""
    stats: sink_mod.WindowStats
    dims: Dims
    chips: int
    setup_s: float
    trace: Optional[dict]
    peaks: Optional[dict]


SPANS = (("prefill_into", "bench.prefill"),
         ("decode_step", "bench.decode_step"),
         ("_sample", "bench.sample"),
         ("_keys", "bench.sample_keys"))
TABLE_SPANS = (("prepare_step", "bench.page_table"),
               ("release", "bench.page_table"))


def _span(fn, name):
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


def _annotate(engine) -> None:
    """Host spans around the engine's calls, on this instance only."""
    for attr, name in SPANS:
        setattr(engine, attr, _span(getattr(engine, attr), name))
    for attr, name in TABLE_SPANS:
        table = engine.page_table
        setattr(table, attr, _span(getattr(table, attr), name))


def _say(err, *a):
    print(*a, file=err, flush=True)


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             traced: bool, t_start: float, require_tpu: bool = True,
             patch_engine: Optional[Callable] = None,
             compile_cache: bool = True,
             out=sys.stdout, err=sys.stderr) -> int:
    wl = bench.workload(workload)
    cfgj = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    gen = bench.generator(mix["generator"])
    metrics = bench.metrics_for(workload, traced)
    chips = int(wl["chips"])

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        _say(err, f"no TPU: JAX found {d0.platform} ({d0.device_kind}); "
             f"this benchmark measures the chip only")
        return 2
    if len(devices) < chips:
        _say(err, f"cell {workload} needs {chips} chips, JAX found "
             f"{len(devices)}")
        return 2
    cache_dir = None
    if compile_cache:
        from repro.launch.compile_cache import use_compile_cache
        cache_dir = use_compile_cache()
        # cache every program, however quick to compile, so that a warm
        # set-up compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    peaks = bench.peaks(d0.device_kind) if (require_tpu or traced) else None
    t_backend = time.perf_counter()

    geometry = cfgj["engine"]
    dm = dims_of(cfgj)
    model, engine = system.build(cfgj, seed, devices[:chips])
    jax.block_until_ready(engine.params)
    t_weights = time.perf_counter()
    lengths = gen.lengths(mix, geometry)
    warm = [np.full((n,), 1, np.int32) for n in system.warm_lengths(
        engine.buckets.ladder, lengths, geometry["page_size"],
        geometry["max_len"])]
    engine.serve(warm, 2)
    if patch_engine is not None:
        patch_engine(engine)
    if traced:
        _annotate(engine)
    jobs = gen.jobs(mix, geometry, dm.vocab, seed)
    job = next(jobs)
    t_ready = time.perf_counter()
    setup_s = t_ready - t_start
    _say(err, f"set-up {setup_s:.3f} s: to backend {t_backend - t_start:.3f}"
         f" s, weights and engine {t_weights - t_backend:.3f} s, warm-up "
         f"{t_ready - t_weights:.3f} s; {clock.compiles} programs compiled "
         f"({clock.seconds:.3f} s), {clock.cache_hits} loaded from {cache_dir}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(trace_dir)
    compiles0 = clock.compiles
    start = time.perf_counter()
    sk = sink_mod.Sink(geometry["slots"], start, start + seconds)
    finished = []
    window = (jax.profiler.TraceAnnotation("bench.window") if traced
              else contextlib.nullcontext())
    with window:
        while time.perf_counter() < sk.deadline:
            sk.begin_job([len(p) for p in job.prompts], job.new_tokens)
            try:
                with (jax.profiler.TraceAnnotation("bench.job") if traced
                      else contextlib.nullcontext()):
                    outs = engine.serve(job.prompts, job.new_tokens,
                                        telemetry=sk)
            except sink_mod.WindowClosed:
                break
            sk.job_done()
            finished += list(zip(job.prompts, outs))
            job = next(jobs)
    stats = sk.close()
    compiles_in_window = clock.compiles - compiles0
    reduced = None
    if traced:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:chips])
    engine.params = None
    del engine, model
    gc.collect()
    if traced:
        from bench import trace as trace_mod
        t0 = time.perf_counter()
        reduced = trace_mod.reduce(trace_mod.read_xplane(
            trace_mod.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        _say(err, f"trace read in {time.perf_counter() - t0:.3f} s")
    _say(err, f"window {stats.seconds:.3f} s: {stats.requests} requests, "
         f"{stats.tokens} tokens, {len(stats.decode_ctx)} decode steps, "
         f"{len(finished)} requests finished in whole jobs; "
         f"{compiles_in_window} programs compiled inside the window")

    run = Run(stats=stats, dims=dm, chips=chips, setup_s=setup_s,
              trace=reduced, peaks=peaks)
    values = {}
    for m in metrics:
        v = bench.metric(m["name"]).read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # --- correct -------------------------------------------------------
    limits = bench.limits(workload)
    bad = check.malformed(finished, mix["new_tokens"], dm.vocab)
    chosen = check.pick(finished, seed, int(mix.get("check_tokens", 1024)))
    t0 = time.perf_counter()
    numbers = {}
    if chosen:
        numbers = check.numbers(check.served_gaps(
            dm, seed, [finished[i] for i in chosen], geometry["max_len"]))
    _say(err, f"reference over {len(chosen)} requests in "
         f"{time.perf_counter() - t0:.3f} s")
    checks = {name: {"value": numbers.get(name), "limit": lim["limit"]}
              for name, lim in limits.items()}
    checks["malformed_requests"] = {"value": bad, "limit": 0}
    checks["requests_compared"] = {"value": len(chosen), "limit": 1,
                                   "at_least": True}
    correct = check.correct(numbers, limits, bad, len(chosen))
    failed = bad + (0 if correct else len(chosen))
    if not limits:
        _say(err, f"no limits for {workload}: numbers {numbers}")

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": chips, "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": stats.requests,
              "failed": int(failed), "metrics": values, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        _say(err, f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), file=out, flush=True)
    return 0
