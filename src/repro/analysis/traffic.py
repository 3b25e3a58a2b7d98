"""Traffic auditor pass: jaxpr-derived bytes vs the analytic model.

Walks the decode step's ClosedJaxpr (:mod:`.jaxpr_walk`), bills any
cache outvar that did not arrive through an in-place chain as a fresh
full write, and compares the per-class byte buckets against
``TrafficModel.static_decode_classes`` at full occupancy (every slot
live at the layer cache length) — the operating point where telemetry's
occupancy-dependent accounting coincides with the structural count of
the lowered computation.  Any class mismatch is an error finding
(``traffic-drift``) that is never baselined: accounting drift between
``serve/telemetry.py`` and what XLA actually lowers fails statically.

``meta_*`` (block tables, length scalars) and ``param_*`` classes are
derived and reported but not gated: block-table indirection is O(pages)
int32 noise telemetry deliberately ignores, and param traffic depends
on dispatch decisions (MoE) the structural walk can't see.

Importing this module imports every ``repro.kernels.*.ops`` module so
their pallas cost handlers register; a pallas call without a handler
surfaces as a ``missing-cost-handler`` error finding.
"""
from __future__ import annotations

from typing import List

# importing the ops modules registers their pallas cost handlers
import repro.kernels.flash_attention.ops    # noqa: F401
import repro.kernels.grouped_matmul.ops     # noqa: F401
import repro.kernels.paged_attention.ops    # noqa: F401
import repro.kernels.rate_match.ops         # noqa: F401
import repro.kernels.refresh_sim.ops        # noqa: F401
from repro.analysis.artifacts import AuditUnit
from repro.analysis.jaxpr_walk import CLASS_BY_LEAF, WRITE_BUCKET
from repro.analysis.registry import Finding, register_pass

__all__ = ["traffic_pass", "decode_traffic_report", "split_per_device"]

#: classes where the structural count must equal the analytic model
GATED_CLASSES = ("kv_sweep_read", "kv_page_read", "kv_append_write",
                 "state_read", "state_write",
                 "gather_view_read", "gather_view_write")

#: which cache leaf class backs each gated traffic class, per decode
#: cache layout — paged engines bill pools, contiguous the [B, L] cache
#: (gather-view traffic is derived from pool pages, so it splits with
#: the pool's factor)
_SPLIT_LEAF = {
    "contiguous": {"kv_sweep_read": "kv", "kv_page_read": "kv",
                   "kv_append_write": "kv", "gather_view_read": "kv",
                   "gather_view_write": "kv",
                   "state_read": "state", "state_write": "state"},
    "paged": {"kv_sweep_read": "kv_pool", "kv_page_read": "kv_pool",
              "kv_append_write": "kv_pool", "gather_view_read": "kv_pool",
              "gather_view_write": "kv_pool",
              "state_read": "state_pool", "state_write": "state_pool"},
}


def split_per_device(expected, leaf_factors, mode):
    """Split a global per-class decode bill by cache sharding factors.

    ``expected`` is ``TrafficModel.static_decode_classes`` output;
    ``leaf_factors`` maps cache leaf classes to their per-device split
    factor (``analysis.artifacts.sharded_leaf_factors``).  Returns
    ``(per_device, problems)``: per-device bytes for every gated class
    (exact integer division — a class whose global bytes the factor
    does not divide is a problem, because the 'per-device share' would
    be a fiction) plus any indivisibility problems found.
    """
    leaf_for = _SPLIT_LEAF["contiguous" if mode == "contiguous"
                           else "paged"]
    per_device = {}
    problems = []
    for cls in GATED_CLASSES:
        total = int(expected.get(cls, 0))
        if total == 0:
            per_device[cls] = 0
            continue
        factor = int(leaf_factors.get(leaf_for[cls], 1))
        if total % factor:
            problems.append(
                f"{cls}: global {total} bytes/step not divisible by the "
                f"{leaf_for[cls]!r} sharding factor {factor}")
        per_device[cls] = total // factor
    return per_device, problems


def decode_traffic_report(unit: AuditUnit) -> dict:
    """Derive the decode step's per-class bytes and the analytic twin.

    Returns ``{"derived": {...}, "expected": {...}, "match": bool}``
    (cached on ``unit.reports['traffic']``).
    """
    if "traffic" in unit.reports:
        return unit.reports["traffic"]
    art = unit.artifact("decode")
    res = art.walk()
    buckets = dict(res.buckets)
    # cache outvars that are NOT the same buffer as a cache invar are
    # fresh per-step writes (recurrent state, length high-water marks —
    # or a silently copied KV buffer, which the gate would then catch)
    outvars = art.closed_jaxpr.jaxpr.outvars
    taints = res.outvar_taints
    for var, taint, name in zip(outvars, taints, art.out_leaf_names):
        cls = CLASS_BY_LEAF.get(name)
        if cls is None:
            continue                       # logits etc: not cache state
        if taint is not None and taint.inplace:
            continue                       # billed at its scatter/dus
        buckets[WRITE_BUCKET[cls]] += (int(var.aval.size)
                                       * int(var.aval.dtype.itemsize))
    expected = unit.traffic.static_decode_classes(
        [unit.ctx] * unit.live, unit.mode)
    report = {
        "derived": buckets,
        "expected": expected,
        "problems": list(res.problems),
        "match": all(buckets.get(k, 0) == expected[k]
                     for k in GATED_CLASSES) and not res.problems,
    }
    unit.reports["traffic"] = report
    return report


@register_pass("traffic")
def traffic_pass(unit: AuditUnit) -> List[Finding]:
    findings: List[Finding] = []
    art = unit.artifact("decode")
    if art is None:
        return findings
    report = decode_traffic_report(unit)
    for problem in report["problems"]:
        code = ("missing-cost-handler"
                if problem.startswith("missing-cost-handler") else
                "walker-gap")
        findings.append(Finding(
            pass_name="traffic", code=code,
            subject=f"{unit.label}:decode",
            detail=problem))
    for k in GATED_CLASSES:
        got, want = report["derived"].get(k, 0), report["expected"][k]
        if got != want:
            findings.append(Finding(
                pass_name="traffic", code="traffic-drift",
                subject=f"{unit.label}:decode:{k}",
                detail=(f"jaxpr-derived {k} = {got} bytes/step but "
                        f"TrafficModel.static_decode_classes says {want} "
                        f"(live={unit.live}, ctx={unit.ctx}, "
                        f"mode={unit.mode})")))
    return findings
