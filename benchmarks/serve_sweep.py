"""Serve decode-timing sweep: paged gather vs Pallas block-table kernel.

The perf-trajectory harness CI has been missing: serves an identical
mixed-length workload through two paged engines — ``decode_backend=
"gather"`` (materializes the contiguous logical view every step) and
``"pallas_paged"`` (the :mod:`repro.kernels.paged_attention` kernel
reading pages in place, interpret mode on CPU) — and records per-arch
decode steps/sec plus the telemetry byte split (row-exact KV sweep vs
phantom gather traffic vs per-page kernel reads).  Results land in
``BENCH_serve.json`` (schema below), which the CI ``kernels`` job
uploads as a workflow artifact so the numbers accumulate a trajectory
across PRs instead of staying empty.

Absolute CPU timings are hardware noise; the schema keeps them anyway
(trajectory > precision) next to the byte accounting, which is exact.
Generations are asserted identical across backends on every swept arch
— the bench doubles as a parity smoke.

Each row also carries the *static* per-step byte count: the jaxpr-level
audit (:mod:`repro.analysis`) of the very decode executable the sweep
timed, at full occupancy, next to the telemetry split — so the
trajectory captures auditor/telemetry agreement (``static_match``)
per arch and backend, not just throughput.

v4 lands ROADMAP item 3's device-local decode in the trajectory.  The
script forces a 2-device host CPU topology before jax initializes, so
next to the solo gather/pallas rows it times a real ``shard_map``
engine (``shards=2``: slots and pool extents pinned per device, the
kernel reading only its local pool) and asserts its generations match
the solo rows bit-for-bit.  The partitioning dry-run
(``python -m repro.analysis --mesh 8 --mesh 64 --mesh 512
--partition-only``, one subprocess so the forced 512-device topology
never touches the timed engines) becomes a per-row ``mesh_matrix``:
for each audited mesh size, the decode step's per-device HBM bill
under the weak-scaling audit geometry and its total cross-device wire
bytes per device per step — both exact.  The per-device bill must be
identical across the matrix (weak scaling), and with the device-local
layout no pool byte moves cross-device at any size; the analysis CI
gate owns those assertions, the bench keeps the trajectory.

v5 closes the trace loop (ROADMAP item 4): every timed engine also
records its per-step page-access trace (``telemetry.trace``), and each
row carries ``trace_rtc`` — the measured-trace RTC refresh savings
under every :data:`repro.core.placement.PLACEMENT_POLICIES` mapping of
the engine's pools onto a pool-sized DRAM module — plus
``trace_vs_analytic``, the cross-check that the affine cursor fed the
trace's mean per-window row count reproduces the trace-driven savings
(the two access models must agree on a near-stationary decode stream;
drift fails the run).  Traces are also asserted identical across
backends per arch: page residency is scheduling, not kernel choice.

v6 adds the prefix-sharing row (ROADMAP item 2): per arch, a fourth
engine (gather, solo, ``PagedCacheConfig(sharing=...)``) serves a
same-prefix workload — one exact duplicate (the whole-prompt memo's
full prefill skip), one strict-prefix prompt, one unique — next to an
unshared *twin* engine on the identical workload, asserted
bit-identical.  The three baseline variants keep sharing OFF (their
columns stay comparable across the v5→v6 bump; ``"prefix": None``
marks them).  The sharing row carries a ``prefix`` dict: hit vs
written admission bytes (their sum equals the twin's unshared total —
the telemetry exact-sum invariant), COW fork copy bytes, attached page
count, full skips, the ``savings_frac`` headline, and the measured
per-step trace row-set totals for both engines (the shared total can
only shrink).  Window-limited archs (gemma2's local rings,
recurrentgemma's state pages) legitimately share less or nothing —
the CI gate requires at least one row with real hits and a full skip,
not every row.

Schema (``BENCH_serve.json``)::

    {"schema": "serve-decode-v6",
     "rows": [{"arch", "batch", "backend", "shards", "decode_steps",
               "steps_per_sec", "tok_per_sec",
               "kv_read_bytes_per_step", "gather_bytes_per_step",
               "static_bytes_per_step", "static_classes",
               "static_match", "page_size",
               "trace_rtc": {"<policy>": {"refresh_savings",
                                          "alloc_rows", "rows_used",
                                          "mean_rows_touched"}, ...},
               "trace_vs_analytic": {"trace_savings", "affine_savings",
                                     "delta", "match"},
               "mesh_matrix": {"<N>": {"static_per_device_bytes",
                                       "collective_bytes"}, ...},
               "prefix": None | {"hit_bytes", "admit_write_bytes",
                                 "cow_bytes", "hit_pages", "full_skips",
                                 "savings_frac", "trace_step_pages",
                                 "twin_step_pages"}}, ...]}

    python benchmarks/serve_sweep.py [--archs all] [--out BENCH_serve.json]
"""
from __future__ import annotations

if __package__ in (None, ""):
    import _bootstrap  # noqa: F401  (direct invocation: sys.path setup)

import os

# Two host CPU devices for the shard_map row — set before jax imports.
# The solo rows are unaffected (their engines jit on device 0).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import subprocess
import sys
import tempfile

import jax
import numpy as np

from benchmarks.common import emit
from repro.analysis import decode_traffic_report, unit_from_engine
from repro.configs import ARCH_IDS, get_config
from repro.core.placement import (PLACEMENT_POLICIES, build_placement,
                                  fitting_spec)
from repro.core.refresh_sim import simulate, simulate_trace
from repro.core.rtc import Variant
from repro.core.trace import PageAccessTrace, window_masks
from repro.models.transformer import TransformerLM
from repro.serve import (PagedCacheConfig, PrefixSharingConfig, ServeEngine,
                         ServeTelemetry, TrafficModel)

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}

# Default sweep: one arch per cache family (dense GQA append, softcap +
# local/global ring mix, recurrent state pages) keeps the CI step small;
# --archs all covers the zoo.
DEFAULT_ARCHS = ("qwen1.5-0.5b", "gemma2-9b", "recurrentgemma-2b")
PROMPT_LENS = (4, 9, 6, 12)
SERVE_CTX = 4096                  # deployment context, byte constants
PARTITION_MESHES = (8, 64, 512)   # dry-run matrix for mesh_matrix


def partition_dry_run(archs) -> dict:
    """Per-device decode columns from the abstract-mesh dry-run matrix.

    Runs ``python -m repro.analysis --mesh 8 --mesh 64 --mesh 512
    --partition-only`` in a subprocess (it must force the host CPU
    devices before jax initializes — this process's timed engines keep
    their own 2-device topology) and reduces each partition unit to the
    two per-device columns.  Returns ``{(arch, backend): {str(N):
    {"static_per_device_bytes", "collective_bytes"}}}``; empty on
    failure (the columns then read ``None`` — the bench never fails on
    the dry-run itself, the analysis CI gate owns its findings).
    """
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "partition.json")
        cmd = [sys.executable, "-m", "repro.analysis", "--partition-only",
               "--partition-archs", *archs, "--json", out]
        for n in PARTITION_MESHES:
            cmd += ["--mesh", str(n)]
        # drop this process's forced 2-device flag so the subprocess can
        # force the full matrix's device count itself; the child only
        # lowers, so it stays on the CPU whatever platform this process
        # holds (one process per chip)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = " ".join(
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
        if not os.path.exists(out):
            print(f"partition dry-run produced no JSON "
                  f"(exit {proc.returncode}):\n{proc.stderr[-2000:]}",
                  file=sys.stderr)
            return {}
        units = json.load(open(out)).get("partition", {})
    cols = {}
    for label, u in units.items():
        arch, mode, meshN = label.split("/")
        cols.setdefault((arch, mode), {})[meshN.removeprefix("mesh")] = {
            "static_per_device_bytes": sum(u["bill"]["per_device"].values()),
            "collective_bytes": sum(
                row["wire_bytes_per_device"]
                for row in u["ledger"].get("decode", ())),
        }
    return cols


def trace_rtc_columns(trace: PageAccessTrace, table, smoke) -> tuple:
    """(trace_rtc, trace_vs_analytic) for one engine's measured trace.

    The module is sized to the engine's own pools + smoke weights
    (``fitting_spec``) — a trace-scale study; the *policies* are what
    is compared, not absolute module size.  The cross-check replays the
    row-major placement's mean per-window touched-row count through the
    affine ``simulate`` — FULL_RTC's explicit count depends only on the
    per-window accessed-row count inside the allocation, so the two
    access models must agree up to the rounding of that mean.
    """
    geoms = table.stream_geometries()
    pbytes = smoke.param_counts()["total"] * _ITEMSIZE[smoke.dtype]
    spec = fitting_spec(geoms, param_bytes=pbytes)
    cols, cross = {}, None
    for pol in PLACEMENT_POLICIES:
        pl = build_placement(pol, spec, geoms, param_bytes=pbytes)
        masks = window_masks(trace, pl)
        res = simulate_trace(spec, Variant.FULL_RTC, masks=masks,
                             alloc_lo=pl.alloc_lo, alloc_rows=pl.alloc_rows)
        assert res.violations == 0, (pol, res)
        cols[pol] = {
            "refresh_savings": res.refresh_savings,
            "alloc_rows": pl.alloc_rows,
            "rows_used": pl.rows_used(),
            "mean_rows_touched": float(masks.sum(axis=1).mean()),
        }
        if pol == "row-major":
            acc = int(round(masks.sum(axis=1).mean()))
            affine = simulate(
                spec, Variant.FULL_RTC, alloc_rows=pl.alloc_rows,
                rows_accessed_per_window=acc, n_windows=masks.shape[0],
                alloc_lo=pl.alloc_lo)
            delta = abs(affine.refresh_savings - res.refresh_savings)
            cross = {
                "trace_savings": res.refresh_savings,
                "affine_savings": affine.refresh_savings,
                "delta": delta,
                "match": bool(delta <= 0.01),
            }
    return cols, cross


def sweep_arch(arch: str, max_batch: int, new_tokens: int,
               page_size: int) -> list:
    smoke = get_config(arch, smoke=True)
    model = TransformerLM(smoke)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, smoke.vocab_size, (n,)).astype(np.int32)
               for n in PROMPT_LENS]
    traffic = TrafficModel.from_config(get_config(arch), max_len=SERVE_CTX,
                                       page_size=page_size)
    rows, outs, traces = [], {}, {}
    engine_len = 16 + new_tokens
    variants = [("gather", None), ("pallas_paged", None)]
    if len(jax.devices()) >= 2:
        # the shard_map row: slots and pool extents pinned per device on
        # a (data=2, model=1) mesh; the engine auto-selects shards=2
        # from the default (divisible) pool geometry
        from jax.sharding import Mesh

        from repro.dist.sharding import ShardingPolicy
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                    ("data", "model"))
        variants.append(("pallas_paged", mesh))
    for backend, mesh in variants:
        kw = {}
        if mesh is not None:
            kw = dict(mesh=mesh, policy=ShardingPolicy.for_mesh(mesh))
        engine = ServeEngine(
            model, params, max_len=engine_len, max_batch=max_batch,
            paged=PagedCacheConfig(page_size=page_size),
            decode_backend=backend, **kw)
        shards = engine._table.shards
        if mesh is not None:
            assert shards == 2, (
                f"{arch}: mesh engine resolved shards={shards}, "
                f"expected the device-local layout")
        # ctx_scale maps the smoke engine's occupancies onto SERVE_CTX
        # so the row-exact KV sweep and the (occupancy-independent)
        # gather view bytes describe the same deployment context.
        trace = PageAccessTrace(engine._table.stream_names())
        tele = ServeTelemetry(traffic, ctx_scale=SERVE_CTX / engine_len,
                              trace=trace)
        # warm the executables so steps/sec measures the loop, not
        # tracing (no telemetry -> the trace records only the timed run)
        engine.serve([prompts[0]], 2, seed=1)
        outs[(backend, shards)] = engine.serve(prompts, new_tokens, seed=7,
                                               telemetry=tele)
        traces[(backend, shards)] = trace
        trace_rtc, trace_cross = trace_rtc_columns(trace, engine._table,
                                                   smoke)
        n = max(tele.decode_steps, 1)
        # static audit of the exact decode executable this sweep timed
        # (smoke scale, full occupancy) — the agreement bit is the
        # trajectory signal that accounting has not drifted, and on the
        # shard_map row that per-shard bytes x shards bills exactly
        audit = decode_traffic_report(unit_from_engine(engine, arch))
        rows.append({
            "arch": arch,
            "batch": max_batch,
            "backend": backend,
            "shards": shards,
            "decode_steps": tele.decode_steps,
            "steps_per_sec": (tele.decode_steps / tele.decode_time_s
                              if tele.decode_time_s > 0 else 0.0),
            "tok_per_sec": tele.decode_tok_per_s,
            "kv_read_bytes_per_step": tele.kv_read_bytes_total // n,
            "gather_bytes_per_step": (tele.gather_read_bytes_total
                                      + tele.gather_write_bytes_total) // n,
            "static_bytes_per_step": sum(
                audit["derived"].get(k, 0) for k in audit["expected"]),
            "static_classes": {k: audit["derived"].get(k, 0)
                               for k in sorted(audit["expected"])},
            "static_match": bool(audit["match"]),
            "page_size": page_size,
            "trace_rtc": trace_rtc,
            "trace_vs_analytic": trace_cross,
        })
    ref = outs[("gather", 1)]
    for key, got in outs.items():
        if key == ("gather", 1):
            continue
        for i, (a, b) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{arch} request {i}: {key} generations "
                              f"diverged from gather")
    # page residency is pure scheduling — every backend on the same
    # workload must produce the identical page-access trace (the
    # solo/shard_map allocators differ in extent layout, so only the
    # solo rows are compared step for step)
    ref_steps = traces[("gather", 1)].steps
    for key, tr in traces.items():
        if key[1] != 1 or key == ("gather", 1):
            continue
        assert tr.steps == ref_steps, (
            f"{arch}: {key} page trace diverged from gather")
    rows.append(sweep_sharing(arch, model, params, smoke, traffic,
                              max_batch, new_tokens, page_size, engine_len))
    return rows


def sweep_sharing(arch, model, params, smoke, traffic, max_batch,
                  new_tokens, page_size, engine_len) -> dict:
    """The v6 prefix-sharing row: shared engine vs unshared twin.

    Same-prefix workload (duplicate + strict prefix + unique), gather
    backend, solo.  The twin serves the identical prompts with sharing
    off; generations are asserted bit-identical, the telemetry
    exact-sum invariant (hit + written == twin's total) is asserted,
    and the trace's per-step page totals may only shrink.
    """
    rng = np.random.default_rng(1)
    base = rng.integers(0, smoke.vocab_size, (12,)).astype(np.int32)
    prompts = [base, base.copy(), base[:9].copy(),
               rng.integers(0, smoke.vocab_size, (5,)).astype(np.int32)]

    def run(sharing):
        engine = ServeEngine(
            model, params, max_len=engine_len, max_batch=max_batch,
            paged=PagedCacheConfig(page_size=page_size, sharing=sharing),
            decode_backend="gather")
        trace = PageAccessTrace(engine._table.stream_names())
        tele = ServeTelemetry(traffic, ctx_scale=SERVE_CTX / engine_len,
                              trace=trace)
        engine.serve([prompts[-1]], 2, seed=1)      # warm the executables
        out = engine.serve(prompts, new_tokens, seed=7, telemetry=tele)
        return engine, tele, trace, out

    _, _, twin_trace, twin_out = run(None)
    engine, tele, trace, out = run(PrefixSharingConfig())
    for i, (a, b) in enumerate(zip(twin_out, out)):
        np.testing.assert_array_equal(
            a, b, err_msg=f"{arch} request {i}: shared-prefix generation "
                          f"diverged from the unshared twin")
    shared_pages = sum(trace.step_page_counts())
    twin_pages = sum(twin_trace.step_page_counts())
    assert shared_pages <= twin_pages, (
        f"{arch}: sharing grew the trace row set "
        f"({shared_pages} > {twin_pages})")
    n = max(tele.decode_steps, 1)
    audit = decode_traffic_report(unit_from_engine(engine, arch))
    trace_rtc, trace_cross = trace_rtc_columns(trace, engine._table, smoke)
    return {
        "arch": arch,
        "batch": max_batch,
        "backend": "gather",
        "shards": engine._table.shards,
        "decode_steps": tele.decode_steps,
        "steps_per_sec": (tele.decode_steps / tele.decode_time_s
                          if tele.decode_time_s > 0 else 0.0),
        "tok_per_sec": tele.decode_tok_per_s,
        "kv_read_bytes_per_step": tele.kv_read_bytes_total // n,
        "gather_bytes_per_step": (tele.gather_read_bytes_total
                                  + tele.gather_write_bytes_total) // n,
        "static_bytes_per_step": sum(
            audit["derived"].get(k, 0) for k in audit["expected"]),
        "static_classes": {k: audit["derived"].get(k, 0)
                           for k in sorted(audit["expected"])},
        "static_match": bool(audit["match"]),
        "page_size": page_size,
        "trace_rtc": trace_rtc,
        "trace_vs_analytic": trace_cross,
        "prefix": {
            "hit_bytes": tele.prefix_hit_bytes_total,
            "admit_write_bytes": tele.admit_write_bytes_total,
            "cow_bytes": (tele.cow_read_bytes_total
                          + tele.cow_write_bytes_total),
            "hit_pages": engine._table.stats["pages_attached"],
            "full_skips": tele.prefix_full_skips,
            "savings_frac": tele.prefix_hit_frac,
            "trace_step_pages": shared_pages,
            "twin_step_pages": twin_pages,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default=",".join(DEFAULT_ARCHS),
                    help="comma-separated arch ids, or 'all'")
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_serve.json"))
    args = ap.parse_args()
    archs = ARCH_IDS if args.archs == "all" else \
        tuple(a.strip() for a in args.archs.split(",") if a.strip())

    rows = []
    for arch in archs:
        rows.extend(sweep_arch(arch, args.max_batch, args.new_tokens,
                               args.page_size))
    per_device = partition_dry_run(archs)
    for r in rows:
        r.setdefault("prefix", None)     # baseline variants: sharing OFF
        matrix = per_device.get((r["arch"], r["backend"]))
        r["mesh_matrix"] = matrix if matrix else None
    for r in rows:
        us = 1e6 / r["steps_per_sec"] if r["steps_per_sec"] else 0.0
        m8 = (r["mesh_matrix"] or {}).get("8") or {}
        tr = r["trace_rtc"]
        px = r["prefix"]
        emit(f"serve_decode_{r['arch']}_{r['backend']}"
             + (f"_sm{r['shards']}" if r["shards"] > 1 else "")
             + ("_prefix" if px is not None else ""), us,
             f"steps/s={r['steps_per_sec']:.2f} "
             f"kv_read/step={r['kv_read_bytes_per_step']} "
             f"gather/step={r['gather_bytes_per_step']} "
             f"static/step={r['static_bytes_per_step']} "
             f"perdev@8={m8.get('static_per_device_bytes')} "
             f"collective/dev@8={m8.get('collective_bytes')} "
             f"trace_rtc[rm/bi/sc]="
             + "/".join(f"{tr[p]['refresh_savings']:.3f}"
                        for p in PLACEMENT_POLICIES)
             + (f" prefix_hit={px['savings_frac']:.3f} "
                f"skips={px['full_skips']}" if px is not None else "")
             + f" audit={'ok' if r['static_match'] else 'DRIFT'}")
    if not all(r["static_match"] for r in rows):
        raise SystemExit("static audit disagrees with telemetry — "
                         "run python -m repro.analysis for the class diff")
    if not any(r["shards"] > 1 for r in rows):
        raise SystemExit("no shard_map row was swept — the forced "
                         "2-device topology did not take effect")
    if not all(r["trace_vs_analytic"]["match"] for r in rows):
        bad = [(r["arch"], r["backend"], r["trace_vs_analytic"])
               for r in rows if not r["trace_vs_analytic"]["match"]]
        raise SystemExit(f"trace-driven refresh savings diverged from the "
                         f"affine model on equivalent inputs: {bad}")
    px_rows = [r["prefix"] for r in rows if r["prefix"] is not None]
    if not px_rows:
        raise SystemExit("no prefix-sharing row was swept")
    if not any(p["hit_bytes"] > 0 and p["full_skips"] >= 1
               for p in px_rows):
        raise SystemExit(
            "no swept arch realized prefix hits + a full prefill skip — "
            f"the sharing path regressed: {px_rows}")
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump({"schema": "serve-decode-v6", "rows": rows}, f, indent=1)
    print(f"wrote {out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
