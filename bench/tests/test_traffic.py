"""The job generator: deterministic per seed, the same work every seed."""
import json

import numpy as np

from bench.spec import BENCH_DIR, _load

GEN = _load(BENCH_DIR / "traffic" / "gen_jobs.py")
CHAT = json.loads((BENCH_DIR / "traffic" / "chat.json").read_text())
GEOM = {"slots": 32, "max_len": 1024}


def _take(seed, n=2):
    it = GEN.jobs(CHAT, GEOM, 151936, seed)
    return [next(it) for _ in range(n)]


def test_same_seed_same_jobs():
    a, b = _take(2 ** 33 + 5), _take(2 ** 33 + 5)
    for x, y in zip(a, b):
        assert len(x.prompts) == len(y.prompts) == 32
        assert all(np.array_equal(p, q) for p, q in zip(x.prompts, y.prompts))


def test_every_seed_and_job_holds_the_same_lengths_in_another_order():
    a, b = _take(1), _take(2)
    lens = [sorted(len(p) for p in j.prompts) for j in a + b]
    assert all(x == lens[0] for x in lens)
    assert [len(p) for p in a[0].prompts] != [len(p) for p in b[0].prompts]
    assert lens[0] == GEN.lengths(CHAT, GEOM)
    assert min(lens[0]) >= 1 and max(lens[0]) <= 1024 - 338
    # the exponential law's quantiles keep the published mean, 161.31
    assert abs(np.mean(lens[0]) - CHAT["prompt_mean"]) < 5
