"""Static analyzer: golden findings on hand-built jaxprs + engine audits.

Three layers, cheapest first:

* **Walker goldens** — tiny ``jax.make_jaxpr`` programs exercising one
  billing rule each (structural ops free, compute reads billed, gather
  materializes the view, scatter/dus stays in-place, scan multiplies,
  missing pallas cost handler reported).
* **Pass goldens** — hand-built :class:`Artifact`/:class:`AuditUnit`
  objects that force exactly one finding per registered pass (traffic
  drift, GSPMD gather around a pallas call, unsharded pool page dim,
  donation / large-constant / f64 hygiene), pinning the finding *keys*
  the baseline machinery gates on.
* **Engine cross-checks** — real engines (abstract params, trace only:
  nothing executes) across archs x decode backends must derive byte
  counts equal to ``TrafficModel.static_decode_classes`` class for
  class, and produce zero error findings on a solo topology.
* **HLO collective goldens** (PR 7) — hand-written partitioned-HLO
  lines, one per collective kind plus the iota/explicit/empty
  replica-group forms, async start/done pairs and layout-paren
  operands, pinning the parser's exact per-device wire-byte arithmetic
  and the tensor-family classification the locality lint gates on.
* **Partition gates** (PR 7) — mesh-scoped baseline accounting
  (``@mesh=N`` keys), the per-device bill splitter, and the invariance
  gate on synthetic units; the real 2-vs-8-vs-64 cross-check lowers
  engines in a subprocess (forced device count) under ``slow_serve``.

The 2-device GSPMD-gather detection lives in
``test_serve_multidevice.py`` (it needs a forced device count before
jax initializes, hence a subprocess).
"""
import itertools
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro.analysis import decode_traffic_report, unit_from_engine
from repro.analysis.artifacts import (Artifact, AuditUnit,
                                      sharded_leaf_factors)
from repro.analysis.costs import (KernelCost, lookup_pallas_cost,
                                  register_pallas_cost, uniform_cost)
from repro.analysis.hlo_walk import (classify_collective, ledger_rows,
                                     parse_collectives)
from repro.analysis.jaxpr_walk import (PallasSite, Taint, TRAFFIC_CLASSES,
                                       WalkResult, walk_jaxpr)
from repro.analysis.lints import hygiene_pass, sharding_pass
from repro.analysis.partition import PartitionUnit, invariance_findings
from repro.analysis.registry import (BASELINE_SCHEMA, Finding,
                                     baseline_payload, diff_baseline,
                                     key_in_scope, key_mesh_size,
                                     load_baseline, registered_passes,
                                     run_passes)
from repro.analysis.traffic import (GATED_CLASSES, split_per_device,
                                    traffic_pass)
from repro.configs import get_config
from repro.models.transformer import TransformerLM
from repro.serve import PagedCacheConfig, ServeEngine, TrafficModel

BASELINE = (pathlib.Path(__file__).parent.parent
            / "src/repro/analysis/baseline.json")


def _kv(src=0, **kw):
    return Taint("kv", resident=True, inplace=True, src=src, **kw)


def _bytes(x):
    return int(np.prod(x.shape)) * x.dtype.itemsize


# --------------------------------------------------------------- walker rules
def test_structural_ops_are_free_and_keep_inplace():
    closed = jax.make_jaxpr(lambda k: k.T.reshape(4, 4))(
        jnp.ones((2, 8), jnp.float32))
    res = walk_jaxpr(closed, [_kv()])
    assert all(v == 0 for v in res.buckets.values())
    t = res.outvar_taints[0]
    assert t is not None and t.inplace and t.resident and t.cls == "kv"


def test_compute_read_bills_resident_operand_once():
    k = jnp.ones((2, 8), jnp.float32)
    closed = jax.make_jaxpr(lambda k: (k * 2.0).sum())(k)
    res = walk_jaxpr(closed, [_kv()])
    assert res.buckets["kv_sweep_read"] == _bytes(k)
    # the product is a fresh intermediate: summing it costs nothing
    assert res.outvar_taints[0] is None


def test_dynamic_update_slice_bills_update_bytes_in_place():
    cache = jnp.zeros((8, 4), jnp.float32)
    upd = jnp.ones((1, 4), jnp.float32)
    closed = jax.make_jaxpr(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0)))(
            cache, upd, 3)
    res = walk_jaxpr(closed, [_kv(), None, None])
    assert res.buckets["kv_append_write"] == _bytes(upd)
    assert res.buckets["kv_sweep_read"] == 0      # no full-cache re-read
    t = res.outvar_taints[0]
    assert t is not None and t.inplace            # same buffer flows out


def _page_loop(update):
    """A data-dependent loop writing ``update(i)`` into page ``i`` of a
    pool, ``n`` times, as the page table's assignment loop does."""
    def f(pool, n):
        return jax.lax.fori_loop(
            0, n, lambda i, p: p.at[i].set(update(i)), pool)
    return jax.make_jaxpr(f)(jnp.zeros((8, 4, 2), jnp.float32), 2)


@pytest.mark.parametrize("fill,cls,gated", [
    (True, "kv_pool", None),
    (False, "kv_pool", "kv_append_write"),
    (True, "state", "state_write"),
], ids=["zero", "data", "state_zero"])
def test_while_bills_one_iteration_of_derived_classes_only(fill, cls, gated):
    """A loop with a data-dependent trip count walks its body once: a
    constant fill of a pool page bills ``fill_write`` (one page) and
    continues the pool's in-place chain; written data would be gated
    ``kv_append_write`` traffic, which such a loop cannot bill.  Only a
    KV pool's fill is derived: a constant written into a state buffer
    stays gated ``state_write``."""
    closed = _page_loop(
        (lambda i: 0.0) if fill else
        (lambda i: jnp.full((4, 2), i, jnp.float32)))
    res = walk_jaxpr(closed, [Taint(cls, src=0), None])
    assert res.outvar_taints[0] is not None and res.outvar_taints[0].inplace
    if gated is None:
        assert res.buckets["fill_write"] == 4 * 2 * 4
        assert res.buckets["kv_append_write"] == 0
        assert res.problems == []
    else:
        assert res.buckets["fill_write"] == 0
        assert res.buckets[gated] == 4 * 2 * 4
        assert res.problems == ["while: unbounded trip count not "
                                f"statically billable ({gated})"]


def test_constant_write_into_state_bills_state_write():
    """Outside any loop, zeroing rows of a state buffer (as release and
    state resets do) bills the buffer's own ``state_write``, not
    ``fill_write``."""
    closed = jax.make_jaxpr(lambda st: st.at[1].set(0.0))(
        jnp.zeros((4, 8), jnp.float32))
    res = walk_jaxpr(closed, [Taint("state", src=0)])
    assert res.buckets["state_write"] == 8 * 4
    assert res.buckets["fill_write"] == 0
    assert res.problems == []


def test_pool_gather_materializes_resident_view():
    pool = jnp.zeros((8, 4, 2), jnp.float32)      # 8 pages
    idx = jnp.array([0, 3, 1])

    def f(pool, idx):
        view = pool[idx]                          # lax.gather
        return (view * 2.0).sum()                 # sweeping the view

    closed = jax.make_jaxpr(f)(pool, idx)
    res = walk_jaxpr(closed, [Taint("kv_pool", src=0), None])
    view_bytes = 3 * 4 * 2 * 4
    assert res.buckets["gather_view_read"] == view_bytes
    assert res.buckets["gather_view_write"] == view_bytes
    assert res.buckets["kv_sweep_read"] == view_bytes


def test_walker_shard_map_bills_per_shard_times_shard_count():
    # device-local decode shape: the body gathers from its LOCAL pool
    # extent; per-shard bytes x the shard count (mesh axes not in
    # `auto`) is the exact global bill for evenly split pool operands
    from jax.sharding import AbstractMesh

    pool = jnp.zeros((8, 4, 2), jnp.float32)      # 4 pages per shard
    idx = jnp.array([0, 3, 1])

    def f(pool, idx):
        view = pool[idx]
        return (view * 2.0).sum()

    smap = jax.shard_map(f, mesh=AbstractMesh((2, 1), ("data", "model")),
                         in_specs=(PartitionSpec("data"), PartitionSpec()),
                         out_specs=PartitionSpec(), check_vma=False)
    closed = jax.make_jaxpr(smap)(pool, idx)
    assert closed.jaxpr.eqns[0].primitive.name == "shard_map"
    res = walk_jaxpr(closed, [Taint("kv_pool", src=0), None])
    per_shard = 3 * 4 * 2 * 4        # the gathered view of a local pool
    assert res.buckets["gather_view_read"] == 2 * per_shard
    assert res.buckets["gather_view_write"] == 2 * per_shard
    assert res.buckets["kv_sweep_read"] == 2 * per_shard


def test_scan_multiplies_body_bytes_by_trip_count():
    w = jnp.ones((4, 4), jnp.float32)
    xs = jnp.zeros((5,), jnp.float32)
    closed = jax.make_jaxpr(
        lambda w, xs: jax.lax.scan(
            lambda c, x: (c + (w * x).sum(), None), 0.0, xs))(w, xs)
    res = walk_jaxpr(closed, [Taint("param", src=0), None])
    assert res.buckets["param_read"] == _bytes(w) * 5


def test_unregistered_pallas_call_is_reported_not_guessed():
    import jax.experimental.pallas as pl

    def _copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def f(x):
        return pl.pallas_call(
            _copy, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    closed = jax.make_jaxpr(f)(jnp.ones((4, 4), jnp.float32))
    res = walk_jaxpr(closed, [_kv()])
    assert any(p.startswith("missing-cost-handler") for p in res.problems)
    (site,) = res.pallas_sites
    assert site.operand_taints[0].cls == "kv"
    assert all(v == 0 for v in res.buckets.values())   # never guesses


# ------------------------------------------------------------- cost handlers
def test_every_repo_kernel_registers_a_cost_handler():
    import repro.analysis.traffic  # noqa: F401  (imports the ops modules)
    for kernel in ("flash_attention", "grouped_matmul", "paged_attention",
                   "rate_match", "refresh_sim"):
        assert lookup_pallas_cost(
            f"_kernel at /x/src/repro/kernels/{kernel}/kernel.py:1"
        ) is not None, kernel


def test_register_pallas_cost_rejects_conflicting_handler():
    register_pallas_cost("tests/nonexistent-kernel/", uniform_cost)
    register_pallas_cost("tests/nonexistent-kernel/", uniform_cost)  # idempotent
    with pytest.raises(ValueError, match="already registered"):
        register_pallas_cost("tests/nonexistent-kernel/",
                             lambda eqn: KernelCost((), ()))


# ------------------------------------------------------- pass golden findings
def _unit(artifact, mode="contiguous", axis_sizes=None, data_axes=(),
          page_size=0, live=2, ctx=32):
    cfg = get_config("qwen1.5-0.5b", smoke=True)
    return AuditUnit(
        label=f"hand/{mode}/solo", cfg_name=cfg.name, mode=mode,
        traffic=TrafficModel.from_config(cfg, ctx, page_size=page_size),
        live=live, ctx=ctx, axis_sizes=dict(axis_sizes or {}),
        data_axes=tuple(data_axes), artifacts=[artifact])


def _artifact(closed, seeds, *, specs=None, donated=None, expect=None,
              consts=(), out_names=None):
    n = len(seeds)
    return Artifact(
        name="decode", closed_jaxpr=closed, seeds=tuple(seeds),
        invar_labels=tuple(f"arg{i}" for i in range(n)),
        arg_specs=tuple(specs or [None] * n),
        donated=tuple(donated or [False] * n),
        expect_donated=tuple(expect or [False] * n),
        out_leaf_names=tuple(out_names
                             or [""] * len(closed.jaxpr.outvars)),
        consts=tuple(consts))


def test_traffic_pass_flags_drift_per_class():
    # a decode step that moves zero cache bytes, against a model that
    # expects a full KV sweep: every non-zero expected class must drift
    closed = jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones((2, 2), jnp.float32))
    unit = _unit(_artifact(closed, [None]))
    findings = traffic_pass(unit)
    codes = {f.code for f in findings}
    assert codes == {"traffic-drift"}
    drifted = {f.subject.rsplit(":", 1)[-1] for f in findings}
    expected = unit.traffic.static_decode_classes([32, 32], "contiguous")
    assert drifted == {k for k in GATED_CLASSES if expected[k] != 0}
    assert "kv_sweep_read" in drifted
    key = next(iter(findings)).key
    assert key.startswith("traffic:traffic-drift:hand/contiguous/solo:decode")


def test_sharding_pass_flags_gspmd_gather_around_pallas_call():
    closed = jax.make_jaxpr(lambda p: p.sum())(jnp.zeros((8, 8, 2, 4)))
    art = _artifact(closed, [Taint("kv_pool", src=0)],
                    specs=[PartitionSpec("data", None, None, None)])
    # inject the walk: one pallas site consuming the sharded pool leaf
    art._walk = WalkResult(
        buckets={c: 0 for c in TRAFFIC_CLASSES},
        pallas_sites=[PallasSite(
            name_and_src="_kernel at /x/src/repro/kernels/paged_attention/"
                         "kernel.py:51",
            multiplier=1,
            operand_taints=(Taint("kv_pool", src=0),),
            operand_shapes=((8, 8, 2, 4),))],
        problems=[], outvar_taints=(None,))
    unit = _unit(art, mode="pallas_paged", axis_sizes={"data": 2, "model": 1},
                 page_size=8)
    findings = sharding_pass(unit)
    gather = [f for f in findings
              if f.code == "gspmd-gather-around-pallas-call"]
    assert len(gather) == 1
    assert gather[0].subject.endswith(":decode:kernels/paged_attention")
    assert "arg0" in gather[0].detail


def test_sharding_pass_skips_manual_shard_map_pallas_sites():
    # same sharded-pool operand as above, but the site sits inside a
    # shard_map region (PallasSite.manual): its operands are device-
    # local by construction, so the GSPMD-gather lint must not fire
    closed = jax.make_jaxpr(lambda p: p.sum())(jnp.zeros((8, 8, 2, 4)))
    art = _artifact(closed, [Taint("kv_pool", src=0)],
                    specs=[PartitionSpec("data", None, None, None)])
    art._walk = WalkResult(
        buckets={c: 0 for c in TRAFFIC_CLASSES},
        pallas_sites=[PallasSite(
            name_and_src="_kernel at /x/src/repro/kernels/paged_attention/"
                         "kernel.py:51",
            multiplier=2,
            operand_taints=(Taint("kv_pool", src=0),),
            operand_shapes=((4, 8, 2, 4),),
            manual=True)],
        problems=[], outvar_taints=(None,))
    unit = _unit(art, mode="pallas_paged", axis_sizes={"data": 2, "model": 1},
                 page_size=8)
    assert [f for f in sharding_pass(unit)
            if f.code == "gspmd-gather-around-pallas-call"] == []


def test_sharding_pass_flags_unsharded_pool_page_dim():
    closed = jax.make_jaxpr(lambda p: p.sum())(jnp.zeros((8, 8, 2, 4)))
    art = _artifact(closed, [Taint("kv_pool", src=0)])   # spec: replicated
    unit = _unit(art, mode="pallas_paged", axis_sizes={"data": 2},
                 data_axes=("data",), page_size=8)
    codes = {f.code for f in sharding_pass(unit)}
    assert "pool-page-dim-unsharded" in codes


def test_sharding_pass_silent_on_single_device():
    closed = jax.make_jaxpr(lambda p: p.sum())(jnp.zeros((8, 8, 2, 4)))
    art = _artifact(closed, [Taint("kv_pool", src=0)])
    assert sharding_pass(_unit(art, axis_sizes={"data": 1})) == []


def test_hygiene_pass_flags_donation_constants_and_f64():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones(4, jnp.float64))
    art = _artifact(closed, [_kv()], expect=[True], donated=[False],
                    consts=(np.zeros(1 << 19, np.float32),))   # 2 MiB
    codes = {f.code for f in hygiene_pass(_unit(art))}
    assert codes == {"undonated-cache-buffer", "large-captured-constant",
                     "f64-promotion"}


def test_hygiene_pass_clean_artifact_is_silent():
    closed = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones(4, jnp.float32))
    art = _artifact(closed, [_kv()], expect=[True], donated=[True])
    assert hygiene_pass(_unit(art)) == []


# --------------------------------------------------------- registry/baseline
def test_all_three_passes_are_registered():
    assert set(registered_passes()) >= {"traffic", "sharding", "hygiene"}
    with pytest.raises(ValueError, match="unknown analysis pass"):
        run_passes([], only=["nonesuch"])


def test_diff_baseline_gates_new_and_stale_not_info():
    base = {"sharding:gspmd:x": "known"}
    known = Finding("sharding", "gspmd", "x", "d")
    new = Finding("traffic", "traffic-drift", "y", "d")
    info = Finding("hygiene", "note", "z", "d", severity="info")
    got_new, fixed = diff_baseline([known, new, info], base)
    assert [f.key for f in got_new] == [new.key] and fixed == []
    # baselined finding fixed -> its entry is stale and must be deleted
    got_new, fixed = diff_baseline([info], base)
    assert got_new == [] and fixed == ["sharding:gspmd:x"]
    # info findings never enter a regenerated baseline
    assert baseline_payload([info])["findings"] == []


def test_checked_in_baseline_is_empty_after_shard_map_drain():
    # PR 6 baselined the single GSPMD-gather finding; PR 7 generalized
    # it into the mesh-parameterized pool-collective family (48 keys at
    # mesh 2/8/64/512); the device-local shard_map decode layout
    # drained every one of them.  The baseline must STAY empty — a new
    # pool collective belongs fixed, not allowlisted, and this test is
    # the tripwire against quietly re-baselining one.
    data = json.loads(BASELINE.read_text())
    assert data["schema"] == BASELINE_SCHEMA
    assert data["findings"] == [], [e["key"] for e in data["findings"]]
    assert load_baseline(BASELINE) == {}


# ------------------------------------------------- engine-level cross-checks
CROSS_ARCHS = ("qwen1.5-0.5b", "gemma2-9b", "recurrentgemma-2b")


def _audit_unit(arch, mode):
    cfg = get_config(arch, smoke=True)
    model = TransformerLM(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    kw = dict(max_len=32, max_batch=2)
    if mode != "contiguous":
        kw.update(paged=PagedCacheConfig(page_size=8), decode_backend=mode)
    return unit_from_engine(ServeEngine(model, params, **kw), arch)


@pytest.mark.parametrize("mode", ("contiguous", "gather", "pallas_paged"))
@pytest.mark.parametrize("arch", CROSS_ARCHS)
def test_static_audit_matches_telemetry_exactly(arch, mode):
    unit = _audit_unit(arch, mode)
    rep = decode_traffic_report(unit)
    assert rep["problems"] == []
    for k in GATED_CLASSES:
        assert rep["derived"].get(k, 0) == rep["expected"][k], (
            f"{arch}/{mode}: {k} derived {rep['derived'].get(k, 0)} "
            f"!= telemetry {rep['expected'][k]}")
    # solo topology: no pass may produce an error finding
    errors = [f for f in run_passes([unit]) if f.severity == "error"]
    assert errors == [], [f.key for f in errors]


# ------------------------------------------------------ HLO collective goldens
_FRAME_IDS = itertools.count(1)


def _meta(op_name, source_file, source_line):
    """Collective metadata naming a fresh stack frame, followed by the
    module tables that resolve it (the parser merges tables by id)."""
    i = next(_FRAME_IDS)
    return (f'metadata={{op_name="{op_name}" stack_frame_id={i}}}\n'
            f'FileNames\n{i} "{source_file}"\n'
            f'FileLocations\n{i} {{file_name_id={i} function_name_id={i} '
            f'line={source_line} end_line={source_line}}}\n'
            f'StackFrames\n{i} {{file_location_id={i} parent_frame_id={i}}}\n')


def _one(line, n_devices=None):
    (c,) = parse_collectives(line, n_devices=n_devices)
    return c


def test_all_gather_explicit_groups_and_ring_bytes():
    c = _one(
        '  %all-gather.1 = f32[8,16]{1,0} all-gather(f32[2,16]{1,0} %p.0), '
        'channel_id=1, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, '
        'use_global_device_ids=true, '
        + _meta("jit(decode)/jit(main)/while/body/gather",
                   "/repo/src/repro/models/attention.py", 336))
    assert (c.kind, c.n_groups, c.group_size) == ("all-gather", 2, 4)
    assert c.result_bytes == 8 * 16 * 4 and c.operand_bytes == 2 * 16 * 4
    # ring all-gather: each device wires out*(g-1)/g bytes
    assert c.wire_bytes_per_device() == 8 * 16 * 4 * 3 // 4
    assert c.source_file.endswith("attention.py") and c.source_line == 336
    assert classify_collective(c, "gather") == "kv_pool"
    assert classify_collective(c, "contiguous") == "kv"


def test_all_reduce_iota_groups_and_state_classification():
    c = _one(
        '  %all-reduce.2 = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %x), '
        'channel_id=2, replica_groups=[2,4]<=[4,2]T(1,0), to_apply=%add, '
        + _meta("jit(decode)/jit(main)/while/body/gather",
                   "/repo/src/repro/models/rglru.py", 151))
    assert (c.kind, c.n_groups, c.group_size) == ("all-reduce", 2, 4)
    # ring all-reduce = reduce-scatter + all-gather: 2*in*(g-1)/g
    assert c.wire_bytes_per_device() == 2 * (4 * 4 * 4) * 3 // 4
    assert classify_collective(c, "pallas_paged") == "state_pool"
    assert classify_collective(c, "contiguous") == "state"


def test_reduce_scatter_metadata_less_float_is_activation():
    c = _one(
        '  %reduce-scatter.3 = f32[1,16]{1,0} reduce-scatter('
        'f32[8,16]{1,0} %y), replica_groups={{0,1,2,3,4,5,6,7}}, '
        'dimensions={0}, to_apply=%add')
    assert (c.kind, c.group_size) == ("reduce-scatter", 8)
    assert c.wire_bytes_per_device() == 8 * 16 * 4 * 7 // 8
    # a GSPMD reshard of an unnamed intermediate: never 'other' (which
    # would be an error finding), never silently a pool class
    assert classify_collective(c, "gather") == "activation"


def test_all_to_all_integer_payload_is_meta():
    c = _one(
        '  %all-to-all.4 = s32[4]{0} all-to-all(s32[4]{0} %idx), '
        'replica_groups={{0,1},{2,3}}, dimensions={0}, '
        + _meta("jit(decode)/jit(main)/while/body/all_to_all",
                   "/repo/src/repro/models/attention.py", 100))
    assert (c.kind, c.n_groups, c.group_size) == ("all-to-all", 2, 2)
    assert c.wire_bytes_per_device() == 4 * 4 * 1 // 2
    # integer payload = block-table/length indirection, even at a KV site
    assert classify_collective(c, "gather") == "meta"


def test_collective_permute_wires_full_operand():
    c = _one(
        '  %collective-permute.5 = f32[2,8]{1,0} collective-permute('
        'f32[2,8]{1,0} %w), channel_id=5, source_target_pairs={{0,1},{1,0}}, '
        + _meta("jit(prefill)/while/body/slice",
                   "/repo/src/repro/models/layers.py", 40))
    assert c.kind == "collective-permute"
    # point-to-point: the whole shard moves, group arithmetic is moot
    assert c.wire_bytes_per_device() == 2 * 8 * 4
    assert classify_collective(c, "gather") == "params"


def test_async_start_counts_once_done_is_skipped():
    text = (
        '  %all-gather-start.6 = (f32[2,4]{1,0}, f32[8,4]{1,0}) '
        'all-gather-start(f32[2,4]{1,0} %z), replica_groups={{0,1,2,3}}, '
        'dimensions={0}\n'
        '  %all-gather-done.7 = f32[8,4]{1,0} all-gather-done('
        '(f32[2,4]{1,0}, f32[8,4]{1,0}) %all-gather-start.6)\n')
    (c,) = parse_collectives(text)
    assert c.is_async and c.kind == "all-gather"
    # async-start result tuple is (operand, gathered): bill the payload
    assert c.result_bytes == 8 * 4 * 4
    assert c.wire_bytes_per_device() == 8 * 4 * 4 * 3 // 4


def test_empty_replica_groups_spans_all_devices_layout_parens_ok():
    # layout annotations put parens inside the operand region — the
    # depth scan must not cut the region short
    c = _one(
        '  %all-reduce.8 = f32[4]{0} all-reduce(f32[4]{0:T(4)} %f), '
        'replica_groups={}, to_apply=%add', n_devices=16)
    assert (c.n_groups, c.group_size) == (1, 16)
    assert c.operand_bytes == 4 * 4
    assert c.wire_bytes_per_device() == 2 * 16 * 15 // 16


def test_pool_dims_fallback_pins_metadata_less_pool_moves():
    c = _one('  %all-gather.9 = f32[40,8,2,4]{3,2,1,0} all-gather('
             'f32[5,8,2,4]{3,2,1,0} %pool), replica_groups={{0,1,2,3,4,5,6,7}}, '
             'dimensions={0}')
    pool_dims = {(40, 8, 2, 4): "kv_pool", (5, 8, 2, 4): "kv_pool"}
    # without the shape map this is just an unnamed float reshard...
    assert classify_collective(c, "pallas_paged") == "activation"
    # ...with it, a whole-pool materialization cannot hide
    assert classify_collective(c, "pallas_paged", pool_dims) == "kv_pool"


def test_transformer_cache_write_sites_classify_as_cache_not_params():
    line = ('  %all-reduce.10 = f32[1,1,32,4,16]{4,3,2,1,0} all-reduce('
            'f32[1,1,32,4,16]{4,3,2,1,0} %dus), replica_groups={{0,1}}, '
            'to_apply=%add, '
            + _meta("jit(prefill)/jit(main)/while/body/"
                       "dynamic_update_slice",
                       "/repo/src/repro/models/transformer.py", 382))
    c = _one(line)
    assert classify_collective(c, "contiguous") == "kv"
    # a non-cache-write transformer.py site stays params
    c2 = _one(line.replace("dynamic_update_slice", "dot_general"))
    assert classify_collective(c2, "contiguous") == "params"


def test_paged_kernel_collectives_get_their_own_ledger_site():
    text = (
        '  %all-gather.11 = f32[40,8,2,4]{3,2,1,0} all-gather('
        'f32[5,8,2,4]{3,2,1,0} %kp), replica_groups={{0,1,2,3,4,5,6,7}}, '
        'dimensions={0}, '
        + _meta("jit(decode)/jit(paged_decode_attention)/while/body/"
                   "dynamic_slice",
                   "/repo/src/repro/kernels/paged_attention/kernel.py", 157)
        + '\n'
        '  %all-gather.12 = f32[40,8,2,4]{3,2,1,0} all-gather('
        'f32[5,8,2,4]{3,2,1,0} %kp2), replica_groups={{0,1,2,3,4,5,6,7}}, '
        'dimensions={0}, '
        + _meta("jit(decode)/jit(paged_decode_attention)/while/body/"
                   "dynamic_slice",
                   "/repo/src/repro/kernels/paged_attention/kernel.py", 157))
    rows = ledger_rows(parse_collectives(text), "pallas_paged")
    (row,) = rows
    assert row["site"] == "kernels/paged_attention"
    assert row["class"] == "kv_pool" and row["count"] == 2
    per_op = 40 * 8 * 2 * 4 * 4 * 7 // 8
    assert row["wire_bytes_per_device"] == 2 * per_op


# ------------------------------------------------------------ partition gates
def test_key_mesh_size_and_scope():
    assert key_mesh_size("partition:pool-collective:x@mesh=512") == 512
    assert key_mesh_size("sharding:gspmd:x") is None
    assert key_mesh_size("pass:code:mesh=8") is None     # suffix only
    # @mesh=N keys are scored iff N was audited
    assert key_in_scope("p:c:x@mesh=8", {2, 8})
    assert not key_in_scope("p:c:x@mesh=512", {2, 8})
    # mesh-independent keys are scored unless the jaxpr matrix was skipped
    assert key_in_scope("sharding:gspmd:x", {2, 8}, unmeshed_in_scope=True)
    assert not key_in_scope("sharding:gspmd:x", {2}, unmeshed_in_scope=False)
    # --partition-archs narrows meshed-key scope to the audited archs:
    # subjects lead with "<arch>/<mode>", so a qwen-only run cannot
    # declare another arch's @mesh=N entries stale
    qwen = "partition:pool-collective:qwen1.5-0.5b/gather:x@mesh=8"
    rg = "partition:pool-collective:recurrentgemma-2b/gather:x@mesh=8"
    assert key_in_scope(qwen, {8}, audited_archs=("qwen1.5-0.5b",))
    assert not key_in_scope(rg, {8}, audited_archs=("qwen1.5-0.5b",))
    assert key_in_scope(rg, {8}, audited_archs=None)   # full matrix ran
    # prefix match is on the full arch token, not a substring
    assert not key_in_scope(
        "partition:pool-collective:qwen1.5-0.5b-xl/gather:x@mesh=8",
        {8}, audited_archs=("qwen1.5-0.5b",))


def test_diff_baseline_leaves_out_of_scope_mesh_entries_alone():
    base = {"partition:pool-collective:x@mesh=2": "n",
            "partition:pool-collective:x@mesh=512": "n",
            "sharding:gspmd:x": "n"}
    at2 = Finding("partition", "pool-collective", "x@mesh=2", "d")
    # a --mesh 2 partition-only run: the @mesh=512 entry is unaudited
    # and the jaxpr matrix never ran — neither may be declared stale
    new, fixed = diff_baseline([at2], base, audited_meshes={2},
                               unmeshed_in_scope=False)
    assert new == [] and fixed == []
    # the full run with both sizes audited DOES retire fixed entries
    new, fixed = diff_baseline([at2], base, audited_meshes={2, 512},
                               unmeshed_in_scope=True)
    assert new == []
    assert fixed == ["partition:pool-collective:x@mesh=512",
                     "sharding:gspmd:x"]


def test_baseline_payload_preserves_out_of_scope_entries():
    f = Finding("partition", "pool-collective", "x@mesh=2", "d")
    payload = baseline_payload(
        [f], notes={f.key: "fresh note"},
        preserve={"partition:pool-collective:x@mesh=512": "kept verbatim"})
    entries = {e["key"]: e["note"] for e in payload["findings"]}
    assert entries == {"partition:pool-collective:x@mesh=2": "fresh note",
                       "partition:pool-collective:x@mesh=512":
                           "kept verbatim"}


def test_split_per_device_divides_exactly_or_complains():
    expected = {c: 0 for c in GATED_CLASSES}
    expected.update(kv_sweep_read=800, kv_append_write=80, state_read=102)
    per_dev, problems = split_per_device(
        expected, {"kv": 8, "state": 4}, "contiguous")
    assert per_dev["kv_sweep_read"] == 100
    assert per_dev["kv_append_write"] == 10
    assert problems == ["state_read: global 102 bytes/step not divisible "
                        "by the 'state' sharding factor 4"]
    # paged modes split by the pool leaf classes instead
    per_dev, problems = split_per_device(
        {**{c: 0 for c in GATED_CLASSES}, "gather_view_read": 64},
        {"kv_pool": 8}, "pallas_paged")
    assert per_dev["gather_view_read"] == 8 and problems == []


def test_sharded_leaf_factors_from_entry_shardings():
    class _Sh:                            # quacks like NamedSharding
        def __init__(self, split):
            self.split = split

        def shard_shape(self, shape):
            return (shape[0] // self.split,) + tuple(shape[1:])

    args = ({"kp": jax.ShapeDtypeStruct((40, 8, 2, 4), jnp.float32),
             "block": jax.ShapeDtypeStruct((8, 4), jnp.int32)},
            jax.ShapeDtypeStruct((8,), jnp.int32))
    shardings = ({"kp": _Sh(8), "block": _Sh(1)}, None)
    factors, problems = sharded_leaf_factors(args, shardings, {0: "cache"})
    assert factors == {"kv_pool": 8, "block": 1} and problems == []
    # two leaves of one class disagreeing on the factor is ill-defined
    args2 = ({"kp": jax.ShapeDtypeStruct((40, 2), jnp.float32),
              "vp": jax.ShapeDtypeStruct((40, 2), jnp.float32)},)
    _, problems = sharded_leaf_factors(
        args2, ({"kp": _Sh(8), "vp": _Sh(4)},), {0: "cache"})
    assert len(problems) == 1 and "kv_pool" in problems[0]


def _punit(mesh_size, per_device, mode="pallas_paged"):
    return PartitionUnit(
        label=f"qwen1.5-0.5b/{mode}/mesh{mesh_size}",
        cfg_name="qwen1.5-0.5b", mode=mode, mesh_size=mesh_size,
        live=mesh_size, ctx=32, collectives={},
        bill={"global": {}, "per_device": per_device, "leaf_factors": {}})


def test_invariance_gate_flags_per_device_growth_only():
    flat = {c: 0 for c in GATED_CLASSES}
    flat.update(kv_sweep_read=128, state_read=32)
    grown = dict(flat, state_read=256)    # state bill grew with the mesh
    ok = invariance_findings([_punit(2, flat), _punit(8, flat),
                              _punit(64, flat)])
    assert ok == []
    bad = invariance_findings([_punit(2, flat), _punit(8, grown)])
    assert [f.code for f in bad] == ["per-device-variance"]
    assert bad[0].subject == "qwen1.5-0.5b/pallas_paged:state_read@mesh=8"
    assert bad[0].severity == "error"
    # different (cfg, mode) pairs never compare against each other
    assert invariance_findings(
        [_punit(2, flat), _punit(8, grown, mode="gather")]) == []


@pytest.mark.slow_serve
def test_partition_bill_invariant_across_real_meshes(tmp_path):
    """2-vs-8-vs-64 on real engine artifacts: lower the qwen matrix
    under abstract meshes in a subprocess (forced device count) and
    assert the per-device decode bill is identical at every size."""
    out = tmp_path / "partition.json"
    repo = pathlib.Path(__file__).parent.parent
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--mesh", "2", "--mesh",
         "8", "--mesh", "64", "--partition-only", "--partition-archs",
         "qwen1.5-0.5b", "--json", str(out)],
        capture_output=True, text=True, env=env, cwd=repo)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    data = json.loads(out.read_text())
    assert not [f for f in data["findings"]
                if f["code"] == "per-device-variance"], proc.stdout
    bills = {}
    for label, u in data["partition"].items():
        arch, mode, mesh = label.split("/")
        bills.setdefault(mode, {})[int(mesh[len("mesh"):])] = \
            u["bill"]["per_device"]
    assert set(bills) == {"contiguous", "gather", "pallas_paged"}
    for mode, by_mesh in bills.items():
        assert set(by_mesh) == {2, 8, 64}
        assert by_mesh[2] == by_mesh[8] == by_mesh[64], mode
        assert any(by_mesh[2].values()), f"{mode}: empty per-device bill"
