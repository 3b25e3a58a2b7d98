"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases pass on the CPU at a tiny size (kernels interpreted).

The script itself only runs at full width on a TPU; these tests drive
its phase functions with a two-layer bf16 ``qwen1.5-0.5b`` smoke config
so a broken phase shows up before a chip call is spent on it.
"""
import dataclasses
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax

from repro.configs import get_config
from repro.models.transformer import TransformerLM

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)
TINY = chip_smoke.Spec(max_len=64, page_size=8, max_batch=4, n_requests=8,
                       prompt_min=4, prompt_max=40, new_tokens=8)


def _tiny_model():
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b", smoke=True),
                              dtype="bfloat16")
    model = TransformerLM(cfg)
    return model, model.init(jax.random.key(0))


def test_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert out.splitlines()[0].startswith("platform=cpu")
    assert '"ok"' not in out


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_pass_at_tiny_size(capsys):
    model, params = _tiny_model()
    chip_smoke.run_one_chip(model, params, TINY, seed=0,
                            clock=chip_smoke.CompileClock())
    out = capsys.readouterr().out
    for phase in ("serve", "kernel_vs_ref", "first_decode", "offload"):
        assert f"phase {phase}: PASS" in out


_FOUR = r"""
import dataclasses, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {src!r}]
import jax
import chip_smoke
from repro.configs import get_config
from repro.models.transformer import TransformerLM
cfg = dataclasses.replace(get_config("qwen1.5-0.5b", smoke=True),
                          dtype="bfloat16")
model = TransformerLM(cfg)
spec = chip_smoke.Spec(**{spec!r})
chip_smoke.run_four_chips(model, model.init(jax.random.key(0)), spec, 0,
                          jax.devices())
"""


def test_four_chip_phase_passes_on_four_host_devices():
    """The ``--chips 4`` path on four forced host devices (a fresh
    process: the device count is fixed when JAX starts)."""
    code = _FOUR.format(root=str(ROOT), src=str(ROOT / "src"),
                        spec=dataclasses.asdict(TINY))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "page_table.shards=4" in proc.stdout
    assert "phase shard_map: PASS" in proc.stdout

