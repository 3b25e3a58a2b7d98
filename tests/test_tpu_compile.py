"""Main-path kernels and the decode step compiled for a described TPU v5e
(no chip attached).

The TPU compiler is installed beside the CPU backend, so the Mosaic
lowering can refuse a kernel here, at no chip time, for what interpret
mode cannot see: block shapes off the (8, 128) tiling, VMEM overuse.
Each kernel case compiles the paged decode kernel at a published model
width (bf16, 16-token pages) and checks that the executable carries the
kernel as a ``tpu_custom_call``.  The compiled decode step at the chat
benchmark cell's shapes is checked to update its KV pools in place: no
copy, slice or restack of a pool, and a page write that touches one
page, not the pool.

All chip-compile tests live in this one file.  The topology is
described inside a module fixture (never at import time): only one
process may load the TPU library, so only the worker that runs this
file loads it, and every worker still collects the same tests.  The
kernels interpret off a TPU backend, so a fixture makes them compile
for the described chip while this module runs.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.models.transformer import TransformerLM

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

    # the pools of three layers stacked, read at one layer's index
    pool = sds((3, n_pages, PAGE, kvh * hd), jnp.bfloat16)
    step = jax.jit(lambda q, kp, vp, blk, pos, layer:
                   paged_kernel.paged_decode_attention(
                       q, kp, vp, blk, pos, layer, cache_len=cache_len,
                       window=window, softcap=cfg.attn_softcap))
    compiled = step.lower(sds((BATCH, kvh, g, hd), jnp.bfloat16), pool, pool,
                          sds((BATCH, n_lp), jnp.int32),
                          sds((BATCH,), jnp.int32),
                          sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the qwen1.5-0.5b.chat benchmark cell: 32 slots of max_ctx 1024 in
# 16-token pages, 2050 pool pages (2048 + ZERO and DUMP)
CELL = dict(batch=32, max_ctx=1024, page_size=16, kv_pages=2050)

_POOL_MOVES = re.compile(
    r"= \S+\[([\d,]*)\]\S* (copy|dynamic-slice|dynamic-update-slice|"
    r"custom-call)\(.*")


def _pool_moves(hlo: str, n_pages: int):
    """HLO instructions (fused or not) that copy, slice, restack or
    allocate a buffer with the pool's page count among its dims."""
    found = []
    for line in hlo.splitlines():
        m = _POOL_MOVES.search(line)
        if m is None or str(n_pages) not in m.group(1).split(","):
            continue
        if m.group(2) == "custom-call" and "AllocateBuffer" not in line:
            continue
        found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module")
def qwen_cell(one_chip):
    model = TransformerLM(get_config("qwen1.5-0.5b"))

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: model.init(jax.random.key(0))))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(**CELL)))
    return model, params, cache


def test_decode_step_updates_pool_in_place(qwen_cell, one_chip):
    """The decode step at the chat cell's shapes moves no pool: the
    stacked pools ride the layer scan's carry, each layer writes its
    rows by scatter into them and the kernel reads them where they lie.
    The page-minor layout or the scan's per-layer slice and restack
    would show here as copies, slices or allocations of pool size."""
    model, params, cache = qwen_cell
    node = cache["groups"][0]
    assert node.kp.shape == (24, CELL["kv_pages"], CELL["page_size"], 1024)
    vec = jax.ShapeDtypeStruct((CELL["batch"],), jnp.int32,
                               sharding=one_chip)
    step = jax.jit(functools.partial(model.decode_step,
                                     decode_backend="pallas_paged"),
                   donate_argnums=(1,))
    hlo = step.lower(params, cache, vec, vec).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _pool_moves(hlo, CELL["kv_pages"]) == []


def test_page_zeroing_touches_one_page(qwen_cell, one_chip):
    """Zeroing one page of every layer's pool, as the page table's
    assignment does, reads and writes about that page (24 x 32 KB),
    not a stride through the whole 1.6 GB pool."""
    _, _, cache = qwen_cell
    pool = cache["groups"][0].kp
    pid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    zero = jax.jit(lambda kp, p: kp.at[:, p].set(0), donate_argnums=(0,))
    cost = zero.lower(pool, pid).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    assert cost["bytes accessed"] < 10e6
