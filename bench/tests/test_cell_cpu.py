"""Whole runs of a cell on the CPU at a tiny size, the chip check
skipped: the sound engine comes out correct against the plain reference,
and a broken timed path, or the float8 control, does not."""
import io
import json
import shutil
import time

import numpy as np
import pytest

from bench import check
from bench.harness import run_cell
from bench.spec import BENCH_DIR, Bench
from bench.weights import dims_of

DATA = BENCH_DIR / "tests" / "data"
#: the tiny dense cell's limit on the widest logit gap: sound runs read
#: 0.0 on every seed tried here, the float8 control 0.23 to 1.21 (seeds
#: 1-3 of test_control_fails)
TINY_LIMIT = 0.05
#: the tiny sparse cell's limit on the mean gap, as for Mixtral: bf16
#: rounding can flip a near-tied expert choice, which moves one token's
#: gap by up to about 1.6 (0.008 over the 192 tokens compared); sound runs
#: read 0.0 to 0.0022 on seeds 1-11 and 2**31+17, the control 0.050 to
#: 0.057
TINY_MOE_MEAN_LIMIT = 0.02


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    bench_dir = root / "bench"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(DATA / "tiny.json", bench_dir / "traffic" / "tiny.json")
    spec = {"configs": [], "workloads": [], "per_layer": [],
            "end_to_end": [{"name": n, "unit": "x"} for n in
                           ("tokens_per_s", "ttft_p90_s", "itl_p95_ms",
                            "setup_s")]}
    for name in ("tiny-dense", "tiny-moe"):
        shutil.copy(DATA / f"{name}.json", bench_dir / "configs")
        spec["configs"].append({"name": name,
                                "file": f"bench/configs/{name}.json"})
        spec["workloads"].append({"name": f"{name}.tiny", "config": name,
                                  "traffic": "tiny", "chips": 1})
        limit = ({"mean_logit_gap": {"limit": TINY_MOE_MEAN_LIMIT}}
                 if name == "tiny-moe" else
                 {"max_logit_gap": {"limit": TINY_LIMIT}})
        (bench_dir / "limits" / f"{name}.tiny.json").write_text(
            json.dumps(limit))
    return Bench(root, spec=spec, bench_dir=bench_dir)


def _run(bench, cell, seed, patch=None):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(bench, cell, seed, 2.0, False, time.perf_counter(),
                  require_tpu=False, patch_engine=patch, compile_cache=False,
                  out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["tiny-dense.tiny", "tiny-moe.tiny"])
def test_sound_engine_is_correct(bench, cell):
    r = _run(bench, cell, 2 ** 31 + 17)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"tokens_per_s", "ttft_p90_s",
                                 "itl_p95_ms", "setup_s"}


def _state_unchanged(engine):
    import jax
    import jax.numpy as jnp
    step = engine.decode_step

    def decode_step(cache, tokens, positions):
        logits, _ = step(jax.tree.map(jnp.copy, cache), tokens, positions)
        return logits, cache
    engine.decode_step = decode_step


def _token_altered(engine):
    sample, vocab = engine._sample, engine.model.cfg.vocab_size

    def altered(*a):
        return (sample(*a) + 1) % vocab
    engine._sample = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(bench, fault):
    r = _run(bench, "tiny-dense.tiny", 5, patch=fault)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("config", ["tiny-dense", "tiny-moe"])
def test_control_fails(bench, config, seed):
    """The float8 reference in the program's place comes out not correct
    under the cell's limits file, by the verdict a run gives."""
    cfgj = json.loads((DATA / f"{config}.json").read_text())
    dm = dims_of(cfgj)
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(4):
        p = rng.integers(0, dm.vocab, (24,)).astype(np.int32)
        reqs.append((p, rng.integers(0, dm.vocab, (8,)).astype(np.int32)))
    ctrl = check.control_gaps(dm, seed, reqs, cfgj["engine"]["max_len"])
    assert ctrl.shape == (32,)
    limits = bench.limits(f"{config}.tiny")
    assert limits
    assert not check.correct(check.numbers(ctrl), limits, 0, len(reqs))
