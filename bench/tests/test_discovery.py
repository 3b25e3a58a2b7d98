"""A configuration, a mix and a metric are found by name in files of
their own: adding one adds files and BENCHMARK.json entries only."""
import json
import shutil

from bench.spec import BENCH_DIR, Bench


def test_new_config_mix_and_metric_from_files(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    # a new configuration, mix and metric: new files only
    cfg = json.loads((bench_dir / "configs" / "qwen1.5-0.5b.json").read_text())
    cfg["name"] = "qwen-other"
    (bench_dir / "configs" / "qwen-other.json").write_text(json.dumps(cfg))
    mix = json.loads((bench_dir / "traffic" / "chat.json").read_text())
    mix["new_tokens"] = 4
    (bench_dir / "traffic" / "short.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "engine.requests.py").write_text(
        "def read(run):\n    return run.stats.requests\n")
    spec = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "qwen-other", "source": "x",
                            "file": "bench/configs/qwen-other.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "qwen-other.short",
                              "config": "qwen-other", "traffic": "short",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "engine.requests", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "admission and batching",
                              "moves": "tokens_per_s",
                              "workloads": ["qwen-other.short"]})
    b = Bench(tmp_path, spec=spec, bench_dir=bench_dir)
    wl = b.workload("qwen-other.short")
    assert b.config(wl["config"])["name"] == "qwen-other"
    mix = b.traffic(wl["traffic"])
    gen = b.generator(mix["generator"])
    job = next(gen.jobs(mix, b.config("qwen-other")["engine"], 100, 3))
    assert job.new_tokens == 4
    names = [m["name"] for m in b.metrics_for("qwen-other.short", True)]
    assert names == ["engine.requests"]

    class Stats:
        requests = 7

    class Run:
        stats = Stats()

    assert b.metric("engine.requests").read(Run()) == 7
    # the cells already there do not see the new metric
    assert "engine.requests" not in [
        m["name"] for m in b.metrics_for("qwen1.5-0.5b.chat", True)]


def test_every_named_file_exists():
    b = Bench(BENCH_DIR.parent)
    for w in b.spec["workloads"]:
        b.config(w["config"])
        b.generator(b.traffic(w["traffic"])["generator"])
    for m in b.spec["end_to_end"] + b.spec["per_layer"]:
        assert callable(b.metric(m["name"]).read)
