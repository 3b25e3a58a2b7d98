"""Mesh-scale partitioning pass: abstract-mesh SPMD lowering + gates.

The repo executes on at most 2 CPU devices, but ROADMAP item 3 needs
evidence at production mesh sizes (8/64/512).  This module produces
that evidence statically: each :class:`PartitionUnit` lowers one engine
configuration's executables (decode step, top prefill bucket,
contiguous insert) under an abstract ``data``-major mesh of N devices,
runs GSPMD partitioning via ``jit.lower(...).compile()`` — nothing
executes; params are ``jax.eval_shape`` abstractions and the compile is
O(module), independent of N — and walks the partitioned HLO with
:mod:`.hlo_walk`.

The mesh is *described* with ``jax.sharding.AbstractMesh``; this jax
version cannot lower on one (``_device_assignment`` is unimplemented),
so :func:`repro.dist.sharding.as_concrete_mesh` binds it to compile-only
host CPU devices, which ``python -m repro.analysis`` forces into
existence (``--xla_force_host_platform_device_count``) before jax
initializes.

Three machine checks come out of each unit:

* a **collective-traffic ledger** — every GSPMD-inserted collective,
  classified by the tensor family it moves with exact per-device wire
  bytes (:func:`repro.analysis.hlo_walk.ledger_rows`);
* a **per-device HBM bill** — ``TrafficModel.static_decode_classes``
  split by the decode step's cache shardings
  (:func:`repro.analysis.traffic.split_per_device`), which
  :func:`invariance_findings` asserts is mesh-size-invariant
  class-for-class across every audited mesh (the audit geometry weak-
  scales: one slot, six KV pages and three state pages per device, so
  the per-device split must not move);
* a **locality lint** — any collective moving a page-pool class
  (``kv_pool``/``state_pool``) is an error finding keyed
  ``partition:pool-collective:...@mesh=N``.  The device-local
  ``shard_map`` decode layout (``PagedCacheConfig.shards``;
  :func:`repro.serve.engine.build_decode_step`) drained the whole
  mesh-parameterized family from ``baseline.json``, so any occurrence
  now fails the gate outright.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

from repro.analysis.hlo_walk import (Collective, POOL_CLASSES,
                                     ledger_rows, parse_collectives)
from repro.analysis.registry import Finding
from repro.analysis.traffic import GATED_CLASSES, split_per_device

__all__ = ["PartitionUnit", "abstract_mesh", "partition_unit",
           "build_partition_units", "partition_findings",
           "invariance_findings", "PARTITION_ARCHS", "PARTITION_MODES",
           "SLOTS_PER_DEVICE", "PAGES_PER_DEVICE", "STATE_PAGES_PER_DEVICE",
           "PAGE_SIZE", "MAX_LEN"]

# Weak-scaling audit geometry: per-device shares are constant, so the
# per-device bill is the invariant under mesh growth.  One decode slot,
# six KV pool pages, and three state pages per device, in the
# device-local layout (``PagedCacheConfig.shards = N``): every device
# owns its own reserved ZERO/DUMP pair plus exactly the resident pages
# of its slot, so both pool page dims are N-divisible AND each shard
# clears the per-shard slot floor — page_size 8, context 32 = 4 pages
# per slot leaves each device 4 resident KV pages (= the floor) and
# 1 state slot behind its 2 reserved state pages.
SLOTS_PER_DEVICE = 1
PAGES_PER_DEVICE = 6
STATE_PAGES_PER_DEVICE = 3
PAGE_SIZE = 8
MAX_LEN = 32

#: default matrix: one attention arch (KV pools) + one recurrent arch
#: (conv/h state pools) x every decode cache mode
PARTITION_ARCHS = ("qwen1.5-0.5b", "recurrentgemma-2b")
PARTITION_MODES = ("contiguous", "gather", "pallas_paged")


def abstract_mesh(n: int):
    """The N-device serving mesh as an ``AbstractMesh`` description
    (data-parallel over slots/pages; the model axis stays 1 — smoke
    configs have too few KV heads to fill one)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((int(n), 1), ("data", "model"))


@dataclasses.dataclass
class PartitionUnit:
    """One engine configuration partitioned at one abstract mesh size."""

    label: str                    # '<arch>/<mode>/mesh<N>'
    cfg_name: str
    mode: str                     # 'contiguous' | 'gather' | 'pallas_paged'
    mesh_size: int
    live: int                     # decode batch (slots) the step lowers for
    ctx: int                      # per-slot context capacity
    collectives: Dict[str, Tuple[Collective, ...]]   # per artifact name
    bill: dict                    # {'global', 'per_device', 'leaf_factors'}
    problems: List[str] = dataclasses.field(default_factory=list)
    #: known pool-buffer shapes -> pool class, so a metadata-less
    #: collective whose operand *is* a pool buffer still classifies
    pool_dims: Dict[Tuple[int, ...], str] = \
        dataclasses.field(default_factory=dict)

    def artifact_mode(self, name: str) -> str:
        """Cache layout of one artifact: prefill/insert always build a
        contiguous cache, only the decode step addresses the pools."""
        return self.mode if name == "decode" else "contiguous"

    def ledger(self) -> Dict[str, List[dict]]:
        return {name: ledger_rows(
                    cols, self.artifact_mode(name),
                    self.pool_dims if name == "decode" else None)
                for name, cols in self.collectives.items()}

    def to_dict(self) -> dict:
        return {"label": self.label, "mesh_size": self.mesh_size,
                "live": self.live, "ctx": self.ctx,
                "bill": self.bill, "problems": list(self.problems),
                "ledger": self.ledger(),
                "collectives": {
                    name: [c.to_dict() for c in cols]
                    for name, cols in self.collectives.items()}}


#: cache pytree leaf names that are pool buffers -> their pool class
_POOL_LEAVES = {"kp": "kv_pool", "vp": "kv_pool",
                "conv_p": "state_pool", "h_p": "state_pool"}


def _pool_dims(entry) -> Dict[Tuple[int, ...], str]:
    """Shape fingerprints of every pool buffer in a decode entry: the
    global dims, the per-device shard dims, and (for stacked layer-group
    leaves) their trailing per-layer dims.  :func:`classify_collective`
    uses these to pin metadata-less collectives that move a whole pool.
    """
    import jax

    from repro.analysis.artifacts import leaf_name

    dims: Dict[Tuple[int, ...], str] = {}
    for argnum, arg in enumerate(entry["args"]):
        if entry["roles"].get(argnum) != "cache":
            continue
        sh = entry["shardings"][argnum] \
            if entry.get("shardings") is not None else None
        leaves = jax.tree_util.tree_flatten_with_path(arg)[0]
        sh_leaves = (jax.tree_util.tree_leaves(sh)
                     if sh is not None else [None] * len(leaves))
        for (path, leaf), s in zip(leaves, sh_leaves):
            cls = _POOL_LEAVES.get(leaf_name(path))
            if cls is None:
                continue
            shapes = [tuple(int(d) for d in leaf.shape)]
            if s is not None and hasattr(s, "shard_shape"):
                shapes.append(tuple(int(d)
                                    for d in s.shard_shape(shapes[0])))
            for shape in list(shapes):
                if len(shape) > 2:
                    shapes.append(shape[1:])   # per-layer slice of a stack
            for shape in shapes:
                dims.setdefault(shape, cls)
    return dims


def partition_unit(model, params, cfg_name: str, mode: str,
                   n: int) -> PartitionUnit:
    """Lower one (arch, mode) engine under an N-device abstract mesh
    and walk the partitioned modules.  ``params`` are abstract."""
    from repro.analysis.artifacts import sharded_leaf_factors
    from repro.serve import PagedCacheConfig, ServeEngine
    from repro.serve.paging import RESERVED_PAGES
    from repro.serve.telemetry import TrafficModel

    paged = None
    if mode != "contiguous":
        # Device-local layout: n_pages = resident + n * RESERVED lands on
        # exactly PAGES_PER_DEVICE * n, so the pool page dim is data-axis
        # divisible (page_spec shards it) and the shard_map decode step
        # addresses only the local extent at every audited mesh size.
        paged = PagedCacheConfig(
            page_size=PAGE_SIZE,
            resident_pages=(PAGES_PER_DEVICE - RESERVED_PAGES) * n,
            state_pages=STATE_PAGES_PER_DEVICE * n,
            shards=n)
    eng = ServeEngine(model, params, max_len=MAX_LEN,
                      max_batch=SLOTS_PER_DEVICE * n,
                      paged=paged,
                      decode_backend=mode if paged is not None else "gather")
    entries = eng.lowered_artifacts(mesh=abstract_mesh(n))

    collectives: Dict[str, Tuple[Collective, ...]] = {}
    decode_entry = None
    for entry in entries:
        compiled = entry["fn"].lower(*entry["args"]).compile()
        collectives[entry["name"]] = tuple(
            parse_collectives(compiled.as_text(), n_devices=n))
        if entry["name"] == "decode":
            decode_entry = entry

    factors, factor_problems = sharded_leaf_factors(
        decode_entry["args"], decode_entry["shardings"],
        decode_entry["roles"])
    page = paged.page_size if paged is not None else 0
    traffic = TrafficModel.from_config(model.cfg, eng.max_ctx,
                                       page_size=page)
    expected = traffic.static_decode_classes(
        [eng.max_ctx] * eng.max_batch, mode)
    per_device, split_problems = split_per_device(expected, factors, mode)
    return PartitionUnit(
        label=f"{cfg_name}/{mode}/mesh{n}", cfg_name=cfg_name, mode=mode,
        mesh_size=n, live=eng.max_batch, ctx=eng.max_ctx,
        collectives=collectives,
        bill={"global": expected, "per_device": per_device,
              "leaf_factors": factors},
        problems=factor_problems + split_problems,
        pool_dims=_pool_dims(decode_entry))


def build_partition_units(archs: Sequence[str], meshes: Sequence[int],
                          modes: Sequence[str] = PARTITION_MODES
                          ) -> List[PartitionUnit]:
    """The partition matrix: archs x modes x mesh sizes (sorted)."""
    import jax

    from repro.configs import get_config
    from repro.models.transformer import TransformerLM

    units = []
    for arch in archs:
        cfg = get_config(arch, smoke=True)
        model = TransformerLM(cfg)
        params = jax.eval_shape(lambda m=model: m.init(jax.random.key(0)))
        for mode in modes:
            for n in sorted(set(int(m) for m in meshes)):
                units.append(partition_unit(model, params, arch, mode, n))
    return units


def partition_findings(unit: PartitionUnit) -> List[Finding]:
    """Ledger + locality-lint findings for one partition unit.

    Pool-class collectives (and unclassified float collectives, which
    would otherwise hide pool traffic behind a renamed source site) are
    errors gated against the baseline; payload collectives on
    non-pool families (contiguous-cache appends, logits/param
    movement) are reported as info; integer ``meta`` indirection stays
    in the JSON ledger only.
    """
    findings: List[Finding] = []
    n = unit.mesh_size
    ledger = unit.ledger()
    for art_name in sorted(ledger):
        for row in ledger[art_name]:
            cls = row["class"]
            subject = (f"{unit.cfg_name}/{unit.mode}:{art_name}:"
                       f"{row['kind']}:{cls}:{row['site']}@mesh={n}")
            prov = " ".join(p for p in
                            (row["op_name"],
                             f"({row['source']})" if row["source"] else "")
                            if p)
            if cls in POOL_CLASSES:
                findings.append(Finding(
                    pass_name="partition", code="pool-collective",
                    subject=subject,
                    detail=(f"{row['count']} {row['kind']}(s) moving "
                            f"{cls} pages cross-device: "
                            f"{row['wire_bytes_per_device']:,} wire "
                            f"bytes/device/step at mesh {n}"),
                    provenance=prov))
            elif cls == "other":
                findings.append(Finding(
                    pass_name="partition", code="unclassified-collective",
                    subject=subject,
                    detail=(f"{row['count']} {row['kind']}(s) moving "
                            f"{row['wire_bytes_per_device']:,} wire "
                            f"bytes/device/step of unattributed float "
                            f"payload at mesh {n} — extend the "
                            f"hlo_walk taxonomy"),
                    provenance=prov))
            elif cls != "meta":
                findings.append(Finding(
                    pass_name="partition", code="collective",
                    subject=subject,
                    detail=(f"{row['count']} {row['kind']}(s) on {cls}: "
                            f"{row['wire_bytes_per_device']:,} wire "
                            f"bytes/device/step at mesh {n}"),
                    provenance=prov, severity="info"))
    for problem in unit.problems:
        findings.append(Finding(
            pass_name="partition", code="indivisible-split",
            subject=f"{unit.cfg_name}/{unit.mode}:decode@mesh={n}",
            detail=problem))
    return findings


def invariance_findings(units: Sequence[PartitionUnit]) -> List[Finding]:
    """Assert the per-device decode bill is mesh-size-invariant.

    For every (arch, mode) audited at 2+ mesh sizes, each gated traffic
    class's per-device bytes must equal the smallest mesh's — any drift
    is an error finding (never baselined: a class whose per-device share
    grows with the mesh is exactly the locality regression ROADMAP
    item 3 forbids).
    """
    by_cfg: Dict[Tuple[str, str], Dict[int, dict]] = {}
    for u in units:
        by_cfg.setdefault((u.cfg_name, u.mode), {})[u.mesh_size] = \
            u.bill["per_device"]
    findings: List[Finding] = []
    for (cfg_name, mode), by_mesh in sorted(by_cfg.items()):
        if len(by_mesh) < 2:
            continue
        ref_n = min(by_mesh)
        ref = by_mesh[ref_n]
        for n in sorted(by_mesh):
            if n == ref_n:
                continue
            for cls in GATED_CLASSES:
                got, want = by_mesh[n].get(cls, 0), ref.get(cls, 0)
                if got != want:
                    findings.append(Finding(
                        pass_name="partition", code="per-device-variance",
                        subject=f"{cfg_name}/{mode}:{cls}@mesh={n}",
                        detail=(f"per-device {cls} = {got} bytes/step at "
                                f"mesh {n} but {want} at mesh {ref_n} — "
                                f"the split is not mesh-size-invariant")))
    return findings
