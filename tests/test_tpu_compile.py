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
page, not the pool.  The Mixtral-8x22B stage's decode and prefill
steps, compiled for the 2x2 host with the model axis over its four
chips, are checked for their collectives (two sums of a ``[slots,
d_model]`` activation per layer, the embedding's sum and the logits'
gather) and for moving no pool and no expert weight.

All chip-compile tests live in this one file.  The topology is
described inside a module fixture (never at import time): only one
process may load the TPU library, so only the worker that runs this
file loads it, and every worker still collects the same tests.  The
kernels interpret off a TPU backend, so a fixture makes them compile
for the described chip while this module runs.
"""
import collections
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist.sharding import ShardingPolicy
from repro.kernels.grouped_matmul import kernel as gmm_kernel
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.models.transformer import TransformerLM
from repro.serve.engine import build_decode_step, build_prefill_step
from repro.serve.paging import PageTable

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
        mp.setattr(gmm_kernel, "pallas_interpret", lambda: False)
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


# the two cells' kernel calls: 32 slots of 64 pages of 16 tokens, 2050
# pool pages; qwen1.5-0.5b's 24 layers of 16 KV heads x 64 (F 1024,
# 16-page blocks) and one chip's share of the Mixtral-8x22B stage, 4
# layers of 2 KV heads x 128 and 6 query heads a KV head (F 256, one
# 64-page block a slot)
CELL_KERNELS = [
    (24, 16, 1, 64, 16),
    (4, 2, 6, 128, 64),
]


def _cell_kernel(layers, kvh, g, hd, one_chip, n_lp=64):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((layers, 32 * n_lp + 2, PAGE, kvh * hd), jnp.bfloat16)
    step = jax.jit(lambda q, kp, vp, blk, pos, layer:
                   paged_kernel.paged_decode_attention(
                       q, kp, vp, blk, pos, layer, cache_len=n_lp * PAGE))
    return step.lower(sds((32, kvh, g, hd), jnp.bfloat16), pool, pool,
                      sds((32, n_lp), jnp.int32), sds((32,), jnp.int32),
                      sds((), jnp.int32))


@pytest.mark.parametrize("layers,kvh,g,hd,ppb", CELL_KERNELS)
def test_paged_kernel_fits_vmem_at_cell_shapes(layers, kvh, g, hd, ppb,
                                               one_chip, monkeypatch):
    """The block walk at each cell's shapes: its block size, one kernel
    call, and a compile within the default scoped VMEM limit (16 MiB on
    a v5e), K and V double buffers included.  The compiler holds a
    kernel to that limit: with blocks whose double buffers alone fill
    it (a 1024-page table at ``BLOCK_BYTES`` 4 MiB) it refuses."""
    assert paged_kernel.pages_per_block(PAGE, kvh * hd, 2, 64) == ppb
    hlo = _cell_kernel(layers, kvh, g, hd, one_chip).compile().as_text()
    assert _custom_calls(hlo) == {"paged_decode_attention": 1}
    monkeypatch.setattr(paged_kernel, "BLOCK_BYTES", 4 * 2 ** 20)
    big = paged_kernel.pages_per_block(PAGE, kvh * hd, 2, 1024)
    assert 2 * 2 * big * PAGE * kvh * hd * 2 >= 16 * 2 ** 20
    jax.clear_caches()
    try:
        with pytest.raises(Exception, match="vmem"):
            _cell_kernel(1, kvh, g, hd, one_chip, n_lp=1024).compile()
    finally:
        jax.clear_caches()


# the qwen1.5-0.5b.chat benchmark cell: 32 slots of max_ctx 1024 in
# 16-token pages, 2050 pool pages (2048 + ZERO and DUMP)
CELL = dict(batch=32, max_ctx=1024, page_size=16, kv_pages=2050)

_POOL_MOVES = re.compile(
    r"= \S+\[([\d,]*)\]\S* (copy|dynamic-slice|dynamic-update-slice|"
    r"custom-call)\(.*")


_DEFS = re.compile(r"%([\w.\-]+) = \S+?\[([\d,]*)\]")
_DUS_UPDATE = re.compile(r"dynamic-update-slice\(%[\w.\-]+, %([\w.\-]+)")


def _moved(hlo: str, pattern):
    """``(line, dims moved)`` of each ``pattern`` match: the result's
    dims, but a dynamic-update-slice's update's — one that writes a page
    in place has a pool-shaped result and moves only the page."""
    dims = {m.group(1): m.group(2) for m in _DEFS.finditer(hlo)}
    for line in hlo.splitlines():
        m = pattern.search(line)
        if m is None:
            continue
        if m.group(2) == "custom-call" and "AllocateBuffer" not in line:
            continue
        moved = m.group(1)
        if m.group(2) == "dynamic-update-slice":
            upd = _DUS_UPDATE.search(line)
            if upd is not None:
                moved = dims.get(upd.group(1), moved)
        yield line.strip()[:160], [int(d) for d in moved.split(",") if d]


def _pool_moves(hlo: str, n_pages: int):
    """HLO instructions (fused or not) that copy, slice, restack or
    allocate a buffer with the pool's page count among its dims."""
    return [line for line, dims in _moved(hlo, _POOL_MOVES)
            if n_pages in dims]


@pytest.fixture(scope="module")
def qwen_cell(topo):
    """The cell's engine step as it is dispatched: the paged table's
    pending assignments ride the decode step's host array."""
    model = TransformerLM(get_config("qwen1.5-0.5b"))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    table = PageTable(model, CELL["batch"], CELL["max_ctx"],
                      CELL["page_size"], None)
    step, psh, csh = build_decode_step(
        model, mesh, ShardingPolicy.for_mesh(mesh), batch=CELL["batch"],
        cache_len=CELL["max_ctx"], per_slot_pos=True,
        cache_factory=table.init_cache, decode_backend="pallas_paged",
        assign=table.apply_assignments)

    def placed(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    params = placed(jax.eval_shape(lambda: model.init(jax.random.key(0))),
                    psh)
    cache = placed(jax.eval_shape(table.init_cache), csh)
    return table, step, params, cache, csh


def test_decode_step_updates_pool_in_place(qwen_cell, one_chip):
    """The decode step at the chat cell's shapes moves no pool: the
    page assignments it is sent zero their pages and write their block
    entries in place, the stacked pools ride the layer scan's carry,
    each layer writes its rows by scatter into them and the kernel
    reads them where they lie.  The page-minor layout or the scan's
    per-layer slice and restack would show here as copies, slices or
    allocations of pool size."""
    table, step, params, cache, _ = qwen_cell
    node = cache["groups"][0]
    assert node.kp.shape == (24, CELL["kv_pages"], CELL["page_size"], 1024)
    assert len(table.kv_streams) == 1
    host = jax.ShapeDtypeStruct((4, CELL["batch"]), jnp.int32,
                                sharding=one_chip)
    hlo = step.lower(params, cache, host).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _pool_moves(hlo, CELL["kv_pages"]) == []


def test_page_zeroing_touches_one_page(qwen_cell, one_chip):
    """The page table's assignment rule (the function the decode step
    and the flush program share), compiled for the cell's 32 slots,
    reads and writes about one page of every layer's pool (24 x 32 KB
    each of K and V) — one iteration of its loop over the assigned
    entries — not a page for every slot (24 MB) nor a stride through
    the whole 1.6 GB pool.  The cost analysis bills a loop's body once,
    so this bound rules out a zeroing vectorized over every slot; that
    the loop runs once for one real entry and 31 ``NO_PAGE`` ones is
    pinned by ``test_paged_cache.py``
    (``test_assignment_zeroes_one_page_and_writes_one_block_entry``)."""
    table, _, _, cache, csh = qwen_cell
    rows = jax.ShapeDtypeStruct((2, 1, CELL["batch"]), jnp.int32,
                                sharding=one_chip)
    flush = jax.jit(table._flush_fn, donate_argnums=(0,), out_shardings=csh)
    cost = flush.lower(cache, rows).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    assert cost["bytes accessed"] < 10e6


def _undonated_zeroing(table, cache, csh, one_chip):
    rows = jax.ShapeDtypeStruct((2, 1, CELL["batch"]), jnp.int32,
                                sharding=one_chip)
    return jax.jit(table._flush_fn, out_shardings=csh).lower(cache, rows)


def _layer_restack(table, cache, csh, one_chip):
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def restack(kp, i):
        page = jax.lax.dynamic_index_in_dim(kp, i, 0)
        return jax.lax.dynamic_update_index_in_dim(kp, page * 2, i, 0)
    return jax.jit(restack, donate_argnums=(0,)).lower(
        cache["groups"][0].kp, layer)


@pytest.mark.parametrize("program,op", [
    (_undonated_zeroing, "copy"),
    (_layer_restack, "dynamic-update-slice"),
], ids=["undonated_zeroing", "layer_restack"])
def test_pool_move_detector_catches_real_moves(qwen_cell, one_chip,
                                               program, op):
    """Negative controls of the detector the in-place tests use: the
    page zeroing compiled without donating the cache must copy a pool,
    and a layer sliced out of the stacked pool and written back is a
    dynamic-update-slice whose update holds every page of the layer.
    Each is reported, as the ``op`` it is, though the second's result
    only has the pool's shape it writes in place."""
    table, _, _, cache, csh = qwen_cell
    hlo = program(table, cache, csh, one_chip).compile().as_text()
    found = _pool_moves(hlo, CELL["kv_pages"])
    assert any(f" {op}(" in line for line in found), found


# the mixtral-8x22b-4L.chat benchmark cell: one 4-layer stage of
# Mixtral-8x22B (global attention, as published) over the four chips of
# a v5e 2x2 host, 32 slots of max_ctx 1024 in 16-token pages
STAGE = dict(layers=4, batch=32, max_ctx=1024, page_size=16, kv_pages=2050)
_COLLECTIVE = re.compile(
    r"= (\S+?)(?:\{[^}]*\})? (all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\(")
_MOVES = re.compile(r"= \S+\[([\d,]*)\]\S* (copy|dynamic-slice|"
                    r"dynamic-update-slice|all-gather|custom-call)\(.*")


def _big_moves(hlo: str, elements: int):
    """Copies, slices, gathers and allocations of at least ``elements``
    elements: one expert's weight slice or more."""
    return [line for line, dims in _moved(hlo, _MOVES)
            if dims and np.prod(dims) >= elements]


def _custom_calls(hlo: str):
    return collections.Counter(
        m.group(1) for m in re.finditer(
            r"%(\w+?)(?:\.\d+)? = .* custom-call\(.*"
            r"custom_call_target=\"tpu_custom_call\"", hlo))


@pytest.fixture(scope="module")
def mixtral_stage(topo):
    cfg = dataclasses.replace(get_config("mixtral-8x22b"),
                              n_layers=STAGE["layers"],
                              attn_pattern=("global",), window_size=None)
    model = TransformerLM(cfg)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    policy = ShardingPolicy.for_mesh(mesh)
    table = PageTable(model, STAGE["batch"], STAGE["max_ctx"],
                      STAGE["page_size"], None)
    step, psh, csh = build_decode_step(
        model, mesh, policy, batch=STAGE["batch"],
        cache_len=STAGE["max_ctx"], per_slot_pos=True,
        cache_factory=table.init_cache, decode_backend="pallas_paged",
        assign=table.apply_assignments)

    def placed(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    params = placed(jax.eval_shape(lambda: model.init(jax.random.key(0))),
                    psh)
    cache = placed(jax.eval_shape(table.init_cache), csh)
    assert cache["groups"][0].kp.shape == (4, STAGE["kv_pages"], 16, 1024)
    rep = NamedSharding(mesh, P())
    host = jax.ShapeDtypeStruct((4, STAGE["batch"]), jnp.int32, sharding=rep)
    decode = step.lower(params, cache, host).compile().as_text()
    pre = build_prefill_step(model, mesh, policy, cache_len=STAGE["max_ctx"],
                             batch=1)[0]
    prefill = pre.lower(
        params, jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=rep)
    ).compile().as_text()
    return cfg, decode, prefill


def test_mixtral_stage_kernels_are_custom_calls(mixtral_stage):
    """The paged kernel runs on each chip's own heads and the grouped
    matmul on each chip's expert share (gate, up and down), in decode
    and in prefill."""
    _, decode, prefill = mixtral_stage
    assert _custom_calls(decode) == {"paged_decode_attention": 1,
                                     "grouped_matmul": 3}
    assert _custom_calls(prefill)["grouped_matmul"] == 3


def test_mixtral_stage_collectives(mixtral_stage):
    """Decode: in the layer loop (compiled once, as a while loop) one
    sum of ``[slots, 1, d_model]`` after attention and one after the
    experts; outside it the embedding's sum and the vocabulary-split
    logits' gather.  The page assignments ahead of it, each chip on its
    share of the pool, are the only other while loop, with no
    collective.  Prefill: the same sums at ``[1, tokens, d_model]`` and
    nothing else."""
    cfg, decode, prefill = mixtral_stage
    d, b = cfg.d_model, STAGE["batch"]
    got = collections.Counter(
        (op, shape) for shape, op in _COLLECTIVE.findall(decode))
    assert got == {("all-reduce", f"bf16[{b},1,{d}]"): 3,
                   ("all-gather", f"f32[{b},{cfg.vocab_size}]"): 1}, got
    assert decode.count("while(") == 2
    got = collections.Counter(
        (op, shape) for shape, op in _COLLECTIVE.findall(prefill))
    assert got == {("all-reduce", f"bf16[1,1024,{d}]"): 3}, got


def test_mixtral_stage_moves_no_pool_and_no_expert_weight(mixtral_stage):
    """No copy, slice, gather or allocation of a pool, or of as much as
    one expert's weight slice on a chip, in either step: the pools are
    updated in place and the grouped matmul reads every layer's stacked
    expert weights where they lie."""
    cfg, decode, prefill = mixtral_stage
    one_slice = cfg.d_model * cfg.d_ff // cfg.moe_virtual_split // 4
    assert _pool_moves(decode, STAGE["kv_pages"]) == []
    assert _big_moves(decode, one_slice) == []
    assert _big_moves(prefill, one_slice) == []
