"""Main-path kernels compiled for a described TPU v5e (no chip attached).

The TPU compiler is installed beside the CPU backend, so the Mosaic
lowering can refuse a kernel here, at no chip time, for what interpret
mode cannot see: block shapes off the (8, 128) tiling, VMEM overuse.
Each case compiles the paged decode kernel at a published model width
(bf16, 16-token pages) and checks that the executable carries the
kernel as a ``tpu_custom_call``.

All chip-compile tests live in this one file.  The topology is
described inside a module fixture (never at import time): only one
process may load the TPU library, so only the worker that runs this
file loads it, and every worker still collects the same tests.  The
kernels interpret off a TPU backend, so a fixture makes them compile
for the described chip while this module runs.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import kernel as paged_kernel

PAGE = 16
BATCH = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def compile_for_tpu():
    """Lower the paged kernel for Mosaic instead of the interpreter.
    Traces cached under either mode are dropped on the way in and out,
    so no other test in this process reuses one."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_kernel, "pallas_interpret", lambda: False)
        jax.clear_caches()
        yield
    jax.clear_caches()


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# arch, cache length, window: qwen's global append cache at serving
# max_len 1024; mixtral's windowed (local) layers at their 4096 window
CASES = [
    ("qwen1.5-0.5b", 1024, None),
    ("mixtral-8x22b", 4096, 4096),
]


@pytest.mark.parametrize("arch,cache_len,window", CASES)
def test_paged_kernel_compiles_for_v5e(arch, cache_len, window, one_chip):
    cfg = get_config(arch)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    g = cfg.n_heads // kvh
    assert window == (cfg.window_size if "local" in cfg.attn_pattern
                      else None)
    n_lp = -(-cache_len // PAGE)
    n_pages = BATCH * n_lp + 2

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n_pages, PAGE, kvh, hd), jnp.bfloat16)
    step = jax.jit(lambda q, kp, vp, blk, pos:
                   paged_kernel.paged_decode_attention(
                       q, kp, vp, blk, pos, cache_len=cache_len,
                       window=window, softcap=cfg.attn_softcap))
    compiled = step.lower(sds((BATCH, kvh, g, hd), jnp.bfloat16), pool, pool,
                          sds((BATCH, n_lp), jnp.int32),
                          sds((BATCH,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
