"""Sharding and compile-hygiene lints over lowered serving artifacts.

Sharding (decode step only, meaningful on >1-device meshes):

* ``gspmd-gather-around-pallas-call`` — a ``pallas_call`` consumes an
  operand that structurally descends from a *sharded* input.  The call
  is opaque to GSPMD, which must all-gather the operand onto every
  device before the kernel and re-shard after — per-step collective
  traffic the byte model does not include.  Calls inside a
  ``shard_map`` region (``PallasSite.manual``) are exempt: their
  operands arrive as device-local shards by construction and GSPMD
  never re-shards them — that is exactly how the paged decode step
  closed this gap (ROADMAP item 3); any *new* unmapped occurrence
  fails CI.
* ``pool-page-dim-unsharded`` — a KV pool leaf whose page dim divides
  the data-axis extent is nevertheless replicated in the lowered
  signature.  The paged cache's whole point on a mesh is that pool
  pages shard; losing that silently multiplies cache footprint by the
  device count.

Hygiene (every artifact):

* ``f64-promotion`` — a float64/complex128 aval anywhere in the lowered
  jaxpr (weak-type creep doubles every byte the traffic model counts).
* ``large-captured-constant`` — closure-captured constants baked into
  the executable above 1 MiB (params must arrive as arguments, or every
  recompile re-embeds them and donation can't apply).
* ``host-sync-point`` — callbacks/infeed primitives that force a device
  sync inside a serving step.
* ``undonated-cache-buffer`` — a cache argument the engine declares as
  step-consumed whose lowered ``args_info`` does not carry donation:
  XLA then copies the full buffer every step, traffic the byte
  accounting (which assumes in-place update) would silently miss.
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np

from repro.analysis.artifacts import Artifact, AuditUnit
from repro.analysis.registry import Finding, register_pass

__all__ = ["sharding_pass", "hygiene_pass"]

_LARGE_CONST_BYTES = 1 << 20
_HOST_SYNC_PRIMS = ("io_callback", "pure_callback", "debug_callback",
                    "callback", "infeed", "outfeed")


def _src(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return ""


def _iter_eqns(jaxpr) -> Iterator:
    """All equations, recursing into nested jaxprs (incl. kernel bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                yield from _iter_eqns(sub)
            elif hasattr(v, "eqns"):
                yield from _iter_eqns(v)
            elif isinstance(v, (tuple, list)):
                for b in v:
                    inner = getattr(b, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        yield from _iter_eqns(inner)


def _spec_axes(spec) -> Tuple:
    """Flatten a PartitionSpec's mesh-axis names (ignoring None dims)."""
    axes = []
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        axes.extend(entry if isinstance(entry, tuple) else (entry,))
    return tuple(axes)


def _kernel_key(name_and_src: str) -> str:
    """Stable kernel identity from name_and_src_info: the kernels/<name>
    path fragment when present, else the raw kernel name."""
    marker = "kernels/"
    i = name_and_src.find(marker)
    if i >= 0:
        frag = name_and_src[i:].split("/")
        if len(frag) >= 2:
            return "/".join(frag[:2])
    return name_and_src.split(" ")[0]


@register_pass("sharding")
def sharding_pass(unit: AuditUnit) -> List[Finding]:
    findings: List[Finding] = []
    art = unit.artifact("decode")
    if art is None:
        return findings
    sharded_axes = {a for a, s in unit.axis_sizes.items() if s > 1}
    if not sharded_axes:
        return findings

    def leaf_sharded(flat_index) -> bool:
        spec = art.arg_specs[flat_index]
        return bool(set(_spec_axes(spec)) & sharded_axes)

    res = art.walk()
    for site in res.pallas_sites:
        if site.manual:
            continue      # shard_map body: operands are already local
        offending = []
        for i, taint in enumerate(site.operand_taints):
            if taint is not None and taint.src is not None \
                    and leaf_sharded(taint.src):
                offending.append(
                    f"operand {i} ({taint.cls}, "
                    f"{art.invar_labels[taint.src]}, "
                    f"shape {site.operand_shapes[i]})")
        if offending:
            findings.append(Finding(
                pass_name="sharding", code="gspmd-gather-around-pallas-call",
                subject=f"{unit.label}:decode:{_kernel_key(site.name_and_src)}",
                detail=("GSPMD all-gathers sharded operands around the "
                        "opaque pallas_call: " + "; ".join(offending)),
                provenance=site.name_and_src))

    data_size = 1
    for a in unit.data_axes:
        data_size *= unit.axis_sizes.get(a, 1)
    if data_size > 1:
        for i, (seed, var) in enumerate(zip(art.seeds,
                                            art.closed_jaxpr.jaxpr.invars)):
            if seed is None or seed.cls != "kv_pool":
                continue
            page_dim = len(var.aval.shape) - 3     # [(G,) pages, P, kvh*hd]
            n_pages = var.aval.shape[page_dim]
            if n_pages % data_size:
                continue                           # legitimately replicated
            spec = art.arg_specs[i]
            entry = (tuple(spec)[page_dim]
                     if spec is not None and page_dim < len(tuple(spec))
                     else None)
            entry_axes = (entry if isinstance(entry, tuple)
                          else (entry,) if entry is not None else ())
            if not (set(entry_axes) & sharded_axes):
                findings.append(Finding(
                    pass_name="sharding", code="pool-page-dim-unsharded",
                    subject=f"{unit.label}:decode:{art.invar_labels[i]}",
                    detail=(f"pool leaf {art.invar_labels[i]} has "
                            f"{n_pages} pages divisible by the data-axis "
                            f"extent {data_size} but spec {spec} leaves "
                            f"the page dim replicated")))
    return findings


def _hygiene_artifact(unit: AuditUnit, art: Artifact) -> List[Finding]:
    findings: List[Finding] = []
    subject = f"{unit.label}:{art.name}"

    seen_f64 = set()
    seen_sync = set()
    for eqn in _iter_eqns(art.closed_jaxpr.jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt in (np.float64, np.complex128) \
                    and eqn.primitive.name not in seen_f64:
                seen_f64.add(eqn.primitive.name)
                findings.append(Finding(
                    pass_name="hygiene", code="f64-promotion",
                    subject=f"{subject}:{eqn.primitive.name}",
                    detail=(f"{eqn.primitive.name} produces {dt} "
                            f"{getattr(aval, 'shape', ())} — double-width "
                            f"promotion in a lowered serving step"),
                    provenance=_src(eqn)))
        name = eqn.primitive.name
        if name in _HOST_SYNC_PRIMS and name not in seen_sync:
            seen_sync.add(name)
            findings.append(Finding(
                pass_name="hygiene", code="host-sync-point",
                subject=f"{subject}:{name}",
                detail=f"{name} forces a host round-trip inside the step",
                provenance=_src(eqn)))

    for idx, const in enumerate(art.consts):
        nbytes = int(getattr(const, "nbytes", 0) or 0)
        if nbytes > _LARGE_CONST_BYTES:
            findings.append(Finding(
                pass_name="hygiene", code="large-captured-constant",
                subject=f"{subject}:const{idx}",
                detail=(f"closure-captured constant #{idx}: "
                        f"{nbytes} bytes {getattr(const, 'dtype', '?')}"
                        f"{getattr(const, 'shape', ())} baked into the "
                        f"executable instead of passed as an argument")))

    for i, (expect, actual) in enumerate(zip(art.expect_donated,
                                             art.donated)):
        if expect and not actual:
            findings.append(Finding(
                pass_name="hygiene", code="undonated-cache-buffer",
                subject=f"{subject}:{art.invar_labels[i]}",
                detail=(f"{art.invar_labels[i]} is a step-consumed cache "
                        f"buffer but the lowered executable does not "
                        f"donate it — XLA copies it every dispatch")))
    return findings


@register_pass("hygiene")
def hygiene_pass(unit: AuditUnit) -> List[Finding]:
    findings: List[Finding] = []
    for art in unit.artifacts:
        findings.extend(_hygiene_artifact(unit, art))
    return findings
