"""Where the persistent compilation cache goes: ``$JAX_COMPILATION_CACHE_DIR``
when set (JAX reads it itself), else the fixed ``<checkout>/.jax_cache``;
no other file sets a cache directory."""
import pathlib

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_honoured(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    was = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was


def test_default_is_the_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    assert use_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_no_other_file_sets_a_cache_dir():
    files = [*(ROOT / "src").rglob("*.py"), ROOT / "chip_smoke.py",
             *(p for d in ("benchmarks", "examples", "tools")
               for p in (ROOT / d).glob("*.py"))]
    setters = sorted(str(p.relative_to(ROOT)) for p in files
                     if "jax_compilation_cache_dir" in p.read_text())
    assert setters == ["src/repro/launch/compile_cache.py"]
