"""The three metrics of the expert layer and the model axis: on synthetic
trace dicts and records checked by hand, on the record of a tiny traced
serve, and silent where the trace or the program has nothing for them.
Also a whole CPU run of a tiny sparse cell on a forced (data=1, model=4)
mesh, through the harness as the chip runs it."""
import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from bench.spec import BENCH_DIR, Bench
from bench.weights import dims_of

ROOT = BENCH_DIR.parent
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
STAGE = dims_of(json.loads((BENCH_DIR / "configs" /
                            "mixtral-8x22b-4L.json").read_text()))
DENSE = dims_of(json.loads((BENCH_DIR / "configs" /
                            "qwen1.5-0.5b.json").read_text()))


@pytest.fixture(scope="module")
def bench():
    return Bench(ROOT)


def run_of(ops=None, steps=(), dims=STAGE, chips=4, busy=1.0):
    trace = None if ops is None else {"ops": dict(ops), "busy_s": busy,
                                      "window_s": 2.0 * busy}
    stats = types.SimpleNamespace(traced_decode_ctx=[list(s) for s in steps])
    return types.SimpleNamespace(trace=trace, stats=stats, dims=dims,
                                 chips=chips, peaks=PEAKS)


def test_expert_need_at_published_widths(bench):
    need = bench.metric("moe_experts_roofline").expert_need
    w = 3 * 6144 * 16384
    # one step of 32 live slots: 64 routed rows a layer, 8 experts read
    flops, nbytes = need(STAGE, [[100] * 32])
    assert flops == 4 * 2 * w * 2 * 32
    assert nbytes == 4 * 2 * w * 8
    assert need(STAGE, [[5, 9], [6, 10]]) == (4 * 2 * w * 2 * 4,
                                              4 * 2 * 2 * w * 8)


def test_moe_experts_roofline_over_the_kernels_time(bench):
    m = bench.metric("moe_experts_roofline")
    steps = [[100] * 32] * 10
    ops = {"grouped_matmul.3 = bf16[128,2048]": 0.2,
           "grouped_matmul.4 = bf16[128,6144]": 0.1,
           "paged_decode_attention.8 = bf16[32,6,256]": 5.0,
           "fusion.1 = bf16[32,6144]": 1.0}
    flops, nbytes = m.expert_need(STAGE, steps)
    least = max(flops / PEAKS["bf16_flops"], nbytes / PEAKS["hbm_bytes_per_s"])
    assert m.read(run_of(ops, steps)) == pytest.approx(100 * least / 0.3)


@pytest.mark.parametrize("run", [
    run_of(None, [[1]]),                                  # untraced
    run_of({"fusion.1 = f32[8]": 1.0}, [[1]]),            # no kernel
    run_of({"grouped_matmul.1 = bf16[8,8]": 1.0}, [[1]], dims=DENSE),
], ids=["no_trace", "no_kernel", "dense"])
def test_moe_experts_roofline_reads_nothing(bench, run):
    assert bench.metric("moe_experts_roofline").read(run) is None


def test_collective_share_of_busy_time(bench):
    m = bench.metric("collective.share")
    ops = {"psum.40 = bf16[32,1,6144]": 0.3,
           "all-gather.5 = f32[32,32768]": 0.1,
           "all-reduce-start.2 = f32[8]": 0.1,
           "fusion.1 = bf16[32,6144]": 2.0,
           "copy.7 = f32[8]": 1.0}
    # 0.5 s of collectives over 4 chips, against 1.25 s busy a chip
    assert m.read(run_of(ops, busy=1.25)) == pytest.approx(10.0)
    assert m.read(run_of({"fusion.1 = f32[8]": 1.0})) == 0.0
    assert m.read(run_of(None)) is None


def _record(counts):
    return types.SimpleNamespace(spans=[], counts=counts)


def test_load_max_ratio_from_counters(bench, monkeypatch):
    m = bench.metric("moe.load_max_ratio")
    monkeypatch.setattr(m, "program_record", lambda: _record(
        {"moe.rows": 6400, "moe.rows_max": 1200}))
    assert m.read(run_of()) == pytest.approx(1200 * 8 / 6400)
    monkeypatch.setattr(m, "program_record",
                        lambda: _record({"serve.decode_steps": 3}))
    assert m.read(run_of()) is None
    monkeypatch.setattr(m, "program_record", lambda: None)
    assert m.read(run_of()) is None


def test_load_max_ratio_of_a_traced_serve(bench, tmp_path):
    """The program's counters, from a tiny traced serve of a sparse
    model: every decode step routes each slot's token (live or not) to
    top_k experts in each layer."""
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM
    from repro.serve import PagedCacheConfig, ServeEngine, spans

    cfg = get_config("mixtral-8x22b", smoke=True)
    eng = ServeEngine(TransformerLM(cfg),
                      TransformerLM(cfg).init(jax.random.key(0)),
                      max_len=32, max_batch=3,
                      paged=PagedCacheConfig(page_size=4),
                      decode_backend="pallas_paged")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (5, 9, 3)]
    eng.serve(prompts, 4)
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.serve(prompts, 4)
    finally:
        jax.profiler.stop_trace()
    counts = spans.recorded().counts
    spans.clear()
    steps = counts["serve.decode_steps"]
    assert counts["moe.rows"] == steps * cfg.n_layers * 3 * 2
    assert counts["moe.rows"] / cfg.n_experts <= counts["moe.rows_max"]
    assert counts["moe.rows_max"] <= steps * cfg.n_layers * 3
    m = bench.metric("moe.load_max_ratio")
    dims = types.SimpleNamespace(experts=cfg.n_experts)
    m.program_record = lambda: _record(counts)
    try:
        ratio = m.read(types.SimpleNamespace(dims=dims))
    finally:
        del m.program_record
    assert 1.0 <= ratio <= cfg.n_experts


_CELL = r"""
import io, json, os, shutil, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import pathlib
root, data, out = map(pathlib.Path, sys.argv[1:4])
sys.path[:0] = [str(root / "src"), str(root)]
from bench.harness import run_cell
from bench.spec import Bench
bench_dir = out / "bench"
shutil.copytree(root / "bench", bench_dir,
                ignore=shutil.ignore_patterns("__pycache__", "tests"))
shutil.copy(data / "tiny.json", bench_dir / "traffic" / "tiny.json")
shutil.copy(data / "tiny-moe-4.json", bench_dir / "configs")
(bench_dir / "limits" / "tiny-moe-4.tiny.json").write_text(
    json.dumps({"max_logit_gap": {"limit": %r}}))
spec = {"configs": [{"name": "tiny-moe-4",
                     "file": "bench/configs/tiny-moe-4.json"}],
        "workloads": [{"name": "tiny-moe-4.tiny", "config": "tiny-moe-4",
                       "traffic": "tiny", "chips": 4}],
        "per_layer": [], "end_to_end": [{"name": n, "unit": "x"} for n in
                                        ("tokens_per_s", "itl_p95_ms")]}
res, err = io.StringIO(), io.StringIO()
rc = run_cell(Bench(out, spec=spec, bench_dir=bench_dir), "tiny-moe-4.tiny",
              2 ** 31 + 29, 2.0, False, time.perf_counter(),
              require_tpu=False, compile_cache=False, out=res, err=err)
print("RC", rc)
print(err.getvalue()[-3000:], file=sys.stderr)
print("LINE", res.getvalue().strip().splitlines()[-1])
"""


#: the tiny four-chip cell's limit on the widest logit gap: it is served
#: in float32 with the reference's epsilon, so sound runs read 0.0 (seeds
#: 2, 5, 7 and 2**31+29, on one device and on four); the float8 control
#: reads 2.13 to 3.44 (seeds 1-3)
TINY4_LIMIT = 0.05


def test_sparse_cell_on_a_four_chip_model_axis_is_correct(tmp_path):
    """The harness, unchanged, runs a tiny sparse cell whose engine
    holds the experts, heads and vocabulary in shares over four CPU
    devices, and its tokens come out correct against the reference."""
    p = subprocess.run(
        [sys.executable, "-c", _CELL % TINY4_LIMIT, str(ROOT),
         str(BENCH_DIR / "tests" / "data"), str(tmp_path)],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "RC 0" in p.stdout, p.stderr[-3000:]
    line = json.loads(p.stdout.split("LINE", 1)[1])
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"tokens_per_s", "itl_p95_ms"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_tiny_four_chip_control_fails(seed):
    """The float8 reference in the program's place is not correct under
    the tiny four-chip cell's limit."""
    from bench import check
    cfgj = json.loads((BENCH_DIR / "tests" / "data" /
                       "tiny-moe-4.json").read_text())
    dm = dims_of(cfgj)
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, dm.vocab, (24,)).astype(np.int32),
             rng.integers(0, dm.vocab, (8,)).astype(np.int32))
            for _ in range(4)]
    ctrl = check.control_gaps(dm, seed, reqs, cfgj["engine"]["max_len"])
    limits = {"max_logit_gap": {"limit": TINY4_LIMIT}}
    assert not check.correct(check.numbers(ctrl), limits, 0, len(reqs))
