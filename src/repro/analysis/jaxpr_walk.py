"""Taint-propagating jaxpr walker: exact per-class HBM byte derivation.

The walker runs over a ClosedJaxpr with *taint seeds* on the top-level
invars (which flattened argument each invar is — a cache leaf, a param
leaf, or plain activation input) and derives, without executing
anything, how many bytes each *traffic class* moves per call.  The
rules mirror how XLA treats the equations:

* **Structural** ops (reshape/transpose/slice/broadcast/convert/
  sharding_constraint/...) are free and propagate taint: they describe
  the same buffer (or a fused view of it), and the *consumer* pays.
* A **compute** equation consuming a *resident* operand (a buffer that
  lives in HBM across steps: cache leaves, params, and the gather
  backend's materialized view) reads that operand's full aval once per
  use.  Compute outputs are fresh intermediates and carry no taint —
  this is what keeps e.g. attention scores from inheriting the KV
  sweep's residency and double-billing every downstream op.
* **gather** from a KV *pool* materializes a logical view: the output
  bytes are both read (from the pool) and written (the copy), and the
  result is a new *resident view* whose later consumption is the
  attention sweep.  Gathers from state pools / block tables / params
  are billed once at the gather and their outputs stay non-resident.
* **scatter / dynamic_update_slice** on a resident operand is an
  in-place append: it writes exactly the update operand's bytes, and
  the output continues the operand's identity (``inplace``), so the
  buffer is never billed as a fresh full-size write at the jaxpr
  boundary.
* a scatter or dynamic_update_slice of a **constant fill** (a
  broadcast literal) into a paged KV pool — the zeroing of a freshly
  assigned page — bills ``fill_write``, a derived class, not the pool's
  write class.  A constant written into any other resident buffer
  (state, block tables) bills that buffer's own class.
* **while** (a data-dependent trip count) walks its body once, as one
  iteration: the page table's assignment loop, one iteration an
  assigned page.  Only derived classes (fills, block tables) may move
  inside one; a gated class there is a walker gap.
* **scan** multiplies its body's bytes by the trip count; cache leaves
  ride through keeping their taint, as xs/ys slices or, for the paged
  KV pools the decode step updates in place, as carry.  Stacking the
  ys back is billed at zero — XLA aliases donated loop buffers in
  place, an assumption the donation hygiene lint guards.
* **pallas_call** is opaque: a registered per-kernel cost handler
  (:mod:`repro.analysis.costs`) supplies per-operand bytes, which are
  classified by operand taint.  A missing handler is itself reported.

Top-level *outvars* that are cache leaves but did **not** arrive
through an in-place chain are billed as full fresh writes — which is
exactly how a silently-copied cache would show up, so accounting drift
and copy regressions surface as cross-check failures rather than
passing unnoticed.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.costs import lookup_pallas_cost

__all__ = ["Taint", "WalkResult", "PallasSite", "walk_jaxpr",
           "CLASS_BY_LEAF", "READ_BUCKET", "WRITE_BUCKET", "TRAFFIC_CLASSES"]

# flattened-leaf name -> taint class (mirrors serve.engine.cache_specs)
CLASS_BY_LEAF = {
    "k": "kv", "v": "kv",                  # contiguous KV buffers
    "kp": "kv_pool", "vp": "kv_pool",      # paged KV pools
    "conv": "state", "h": "state",         # contiguous recurrent state
    "conv_p": "state_pool", "h_p": "state_pool",
    "block": "block", "length": "length",  # paging metadata
}

# taint class -> bucket a *compute read* of a resident operand bills to
READ_BUCKET = {
    "kv": "kv_sweep_read", "kv_view": "kv_sweep_read",
    "kv_pool": "gather_view_read",     # direct pool read == view gather
    "state": "state_read", "state_pool": "state_read",
    "block": "meta_read", "length": "meta_read",
    "param": "param_read",
}

# taint class -> bucket a kernel's DMA of that operand bills to (pools
# read through a block-table index map move page granules, not a view)
KERNEL_READ_BUCKET = dict(READ_BUCKET, kv_pool="kv_page_read")

WRITE_BUCKET = {
    "kv": "kv_append_write", "kv_pool": "kv_append_write",
    "kv_view": "gather_view_write",
    "state": "state_write", "state_pool": "state_write",
    "block": "meta_write", "length": "meta_write",
    "param": "param_write",
}

TRAFFIC_CLASSES = (
    "kv_sweep_read", "kv_page_read", "kv_append_write",
    "state_read", "state_write",
    "gather_view_read", "gather_view_write",
    "meta_read", "meta_write", "param_read", "param_write", "fill_write",
)

#: classes a data-dependent loop may move (per iteration, derived only)
_LOOP_CLASSES = frozenset({"fill_write", "meta_read", "meta_write"})

_STRUCTURAL = frozenset({
    "reshape", "transpose", "squeeze", "expand_dims", "broadcast_in_dim",
    "convert_element_type", "slice", "rev", "copy", "reduce_precision",
    "sharding_constraint", "bitcast_convert_type",
})

_SCATTER = frozenset({"scatter", "scatter-add", "scatter-mul",
                      "scatter-min", "scatter-max"})

_HOST_SYNC = frozenset({"io_callback", "pure_callback", "debug_callback",
                        "callback", "infeed", "outfeed"})


@dataclasses.dataclass(frozen=True)
class Taint:
    """Provenance of one jaxpr var.

    ``resident``: the var names an HBM-resident buffer — compute reads
    of it are DRAM traffic.  ``inplace``: the var is the *same* buffer
    as a top-level input (structural / in-place-update chain), so
    emitting it as an output costs nothing.  ``src``: flat index of the
    top-level invar it descends from (sharding-lint provenance).
    """

    cls: str
    resident: bool = True
    inplace: bool = True
    src: Optional[int] = None


#: taint of a constant fill (a broadcast literal): not resident, and an
#: in-place write of it into a KV pool bills ``fill_write``
_FILL = Taint("fill", resident=False, inplace=False)


@dataclasses.dataclass(frozen=True)
class PallasSite:
    """One pallas_call encountered during the walk (for the sharding
    lint and for reporting): where it is, how often the enclosing loops
    run it, and what flows into each operand.  ``manual``: the call
    sits inside a ``shard_map`` region — its operands are already
    device-local shards, GSPMD never gathers or re-shards them, so the
    gspmd-gather sharding lint does not apply."""

    name_and_src: str
    multiplier: int
    operand_taints: Tuple[Optional[Taint], ...]
    operand_shapes: Tuple[Tuple[int, ...], ...]
    manual: bool = False


@dataclasses.dataclass
class WalkResult:
    buckets: Dict[str, int]
    pallas_sites: List[PallasSite]
    problems: List[str]          # non-fatal walker gaps (become findings)
    outvar_taints: Tuple[Optional[Taint], ...] = ()


def _aval_bytes(aval) -> int:
    return int(aval.size) * int(aval.dtype.itemsize)


def _is_literal(v) -> bool:
    return hasattr(v, "val")     # core.Literal carries .val; Var does not


class _Walker:
    def __init__(self):
        self.buckets: Dict[str, int] = {c: 0 for c in TRAFFIC_CLASSES}
        self.sites: List[PallasSite] = []
        self.problems: List[str] = []

    # -- env helpers -------------------------------------------------------
    @staticmethod
    def _get(env, v) -> Optional[Taint]:
        if _is_literal(v):
            return None
        return env.get(v)

    def _read(self, env, v, mult: int, table=READ_BUCKET) -> None:
        t = self._get(env, v)
        if t is not None and t.resident:
            self.buckets[table[t.cls]] += _aval_bytes(v.aval) * mult

    # -- recursion ---------------------------------------------------------
    def walk(self, jaxpr, env: Dict, mult: int) -> None:
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env, mult)

    def _sub(self, closed, in_taints: Sequence[Optional[Taint]],
             env_out: Dict, outvars, mult: int) -> None:
        """Walk a ClosedJaxpr with the given invar taints; map the body
        outvar taints back onto ``outvars`` in ``env_out``."""
        inner = closed.jaxpr
        env: Dict = {}
        for var, t in zip(inner.invars, in_taints):
            if t is not None:
                env[var] = t
        self.walk(inner, env, mult)
        for outer, var in zip(outvars, inner.outvars):
            t = self._get(env, var)
            if t is not None:
                env_out[outer] = t

    # -- equation rules ----------------------------------------------------
    def _eqn(self, eqn, env: Dict, mult: int) -> None:
        prim = eqn.primitive.name

        if prim == "broadcast_in_dim" and _is_literal(eqn.invars[0]):
            env[eqn.outvars[0]] = _FILL
            return

        if prim in _STRUCTURAL or prim == "dynamic_slice":
            # same buffer, different view: free, taint flows through.
            # dynamic_slice start operands are scalars; bill them only
            # if they are themselves resident metadata.
            for v in eqn.invars[1:]:
                self._read(env, v, mult)
            t = self._get(env, eqn.invars[0])
            if t is not None:
                env[eqn.outvars[0]] = t
            return

        if prim == "gather":
            self._gather(eqn, env, mult)
            return

        if prim in _SCATTER or prim == "dynamic_update_slice":
            self._scatter(eqn, env, mult)
            return

        if prim == "pallas_call":
            self._pallas(eqn, env, mult)
            return

        if prim in ("jit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "remat", "checkpoint"):
            closed = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            if closed is None or not hasattr(closed, "jaxpr"):
                self.problems.append(f"{prim}: no recursable jaxpr param")
                return
            taints = [self._get(env, v) for v in eqn.invars]
            self._sub(closed, taints, env, eqn.outvars, mult)
            return

        if prim == "scan":
            self._scan(eqn, env, mult)
            return

        if prim == "shard_map":
            self._shard_map(eqn, env, mult)
            return

        if prim == "cond":
            self._cond(eqn, env, mult)
            return

        if prim == "while":
            self._while(eqn, env, mult)
            return

        if prim in _HOST_SYNC:
            # hygiene lint reports these; no byte accounting
            return

        # generic compute: resident operands are read, output is fresh
        for v in eqn.invars:
            self._read(env, v, mult)

    def _gather(self, eqn, env: Dict, mult: int) -> None:
        src, idx = eqn.invars[0], eqn.invars[1]
        out = eqn.outvars[0]
        self._read(env, idx, mult)           # resident block tables etc.
        t = self._get(env, src)
        if t is None or not t.resident:
            return
        nbytes = _aval_bytes(out.aval) * mult
        if t.cls == "kv_pool":
            # materialize the logical view: pool pages stream out AND
            # the contiguous copy is written; the view is then the
            # resident buffer attention sweeps.
            self.buckets["gather_view_read"] += nbytes
            self.buckets["gather_view_write"] += nbytes
            env[out] = Taint("kv_view", resident=True, inplace=False)
        else:
            # one-shot billed at the gather (state rows, page ids,
            # embedding rows); the small result is a fresh intermediate
            self.buckets[READ_BUCKET[t.cls]] += nbytes

    def _scatter(self, eqn, env: Dict, mult: int) -> None:
        operand = eqn.invars[0]
        if eqn.primitive.name == "dynamic_update_slice":
            update, indices = eqn.invars[1], eqn.invars[2:]
        else:
            indices, update = [eqn.invars[1]], eqn.invars[2]
        t = self._get(env, operand)
        if t is None or not t.resident:
            for v in eqn.invars:         # plain compute on intermediates
                self._read(env, v, mult)
            return
        for v in indices:
            self._read(env, v, mult)
        self._read(env, update, mult)    # a resident update is re-read
        bucket = ("fill_write" if t.cls == "kv_pool"
                  and self._get(env, update) is _FILL
                  else WRITE_BUCKET[t.cls])
        self.buckets[bucket] += _aval_bytes(update.aval) * mult
        env[eqn.outvars[0]] = t          # in-place chain continues

    def _pallas(self, eqn, env: Dict, mult: int) -> None:
        # the kernel body's "<fn> at <file>:<line>", which names its package
        name_src = str(eqn.params["jaxpr"].debug_info.func_src_info)
        taints = tuple(self._get(env, v) for v in eqn.invars)
        self.sites.append(PallasSite(
            name_and_src=name_src, multiplier=mult,
            operand_taints=taints,
            operand_shapes=tuple(tuple(v.aval.shape) for v in eqn.invars)))
        handler = lookup_pallas_cost(name_src)
        if handler is None:
            self.problems.append(f"missing-cost-handler:{name_src}")
            return
        cost = handler(eqn)
        for v, t, nbytes in zip(eqn.invars, taints, cost.reads):
            if t is not None and t.resident and nbytes:
                self.buckets[KERNEL_READ_BUCKET[t.cls]] += nbytes * mult
        aliases = dict(eqn.params.get("input_output_aliases", ()) or ())
        for out_idx, nbytes in enumerate(cost.writes):
            in_idx = next((i for i, o in aliases.items() if o == out_idx),
                          None)
            if in_idx is None:
                continue                 # fresh output: on-chip result
            t = taints[in_idx]
            if t is not None and t.resident and nbytes:
                self.buckets[WRITE_BUCKET[t.cls]] += nbytes * mult

    def _scan(self, eqn, env: Dict, mult: int) -> None:
        p = eqn.params
        ncon, ncar, length = p["num_consts"], p["num_carry"], p["length"]
        closed = p["jaxpr"]
        inner = closed.jaxpr
        body_env: Dict = {}
        for var, v in zip(inner.invars, eqn.invars):
            t = self._get(env, v)
            if t is not None:
                body_env[var] = t        # xs slices keep the stack's taint
        del ncon, ncar              # invar/outvar orders are already 1:1
        self.walk(inner, body_env, mult * int(length))
        # carries map through; ys keep the body outvar's taint — the
        # stack-back is free under the loop-aliasing assumption the
        # donation lint guards.
        for outer, var in zip(eqn.outvars, inner.outvars):
            t = self._get(body_env, var)
            if t is not None:
                env[outer] = t

    def _while(self, eqn, env: Dict, mult: int) -> None:
        """One iteration of a data-dependent loop; carries map through
        like a scan's.  A gated class moving inside it is a gap."""
        p = eqn.params
        taints = [self._get(env, v) for v in eqn.invars[p["cond_nconsts"]:]]
        before = dict(self.buckets)
        self._sub(p["body_jaxpr"], taints, env, eqn.outvars, mult)
        moved = [c for c in TRAFFIC_CLASSES if c not in _LOOP_CLASSES
                 and self.buckets[c] != before[c]]
        if moved:
            self.problems.append(
                f"while: unbounded trip count not statically billable "
                f"({', '.join(moved)})")

    def _shard_map(self, eqn, env: Dict, mult: int) -> None:
        """Manual-mesh (shard_map) region: walk the body once on its
        per-shard avals and multiply by the shard count (mesh axes not
        in ``auto``), so per-shard bytes x shards == the exact global
        bill for evenly split operands — pools, block tables, tokens —
        which are the gated classes.  Replicated operands (params) bill
        their per-device copy x shards, the true all-device HBM figure
        (``param_*`` is derived-only, never gated).  Taints map through
        invars/outvars exactly like a pjit call, so pool in-place chains
        survive the region; pallas sites inside are flagged ``manual``
        for the sharding lint."""
        p = eqn.params
        inner = p["jaxpr"]               # an open Jaxpr, not a ClosedJaxpr
        auto = p.get("auto") or frozenset()
        shards = 1
        for name, size in dict(p["mesh"].shape).items():
            if name not in auto:
                shards *= int(size)
        body_env: Dict = {}
        for var, v in zip(inner.invars, eqn.invars):
            t = self._get(env, v)
            if t is not None:
                body_env[var] = t
        n0 = len(self.sites)
        self.walk(inner, body_env, mult * shards)
        for i in range(n0, len(self.sites)):
            self.sites[i] = dataclasses.replace(self.sites[i], manual=True)
        for outer, var in zip(eqn.outvars, inner.outvars):
            t = self._get(body_env, var)
            if t is not None:
                env[outer] = t

    def _cond(self, eqn, env: Dict, mult: int) -> None:
        branches = eqn.params["branches"]
        taints = [self._get(env, v) for v in eqn.invars[1:]]
        merged: Dict[str, int] = {}
        out_taints = None
        for br in branches:
            sub = _Walker()
            sub_env: Dict = {}
            sub._sub(br, taints, sub_env, eqn.outvars, 1)
            self.sites.extend(
                dataclasses.replace(s, multiplier=s.multiplier * mult)
                for s in sub.sites)
            self.problems.extend(sub.problems)
            for k, v in sub.buckets.items():
                merged[k] = max(merged.get(k, 0), v)
            br_out = tuple(sub_env.get(o) for o in eqn.outvars)
            out_taints = br_out if out_taints is None else tuple(
                a if a == b else None for a, b in zip(out_taints, br_out))
        for k, v in merged.items():
            self.buckets[k] += v * mult          # worst-case branch
        for o, t in zip(eqn.outvars, out_taints or ()):
            if t is not None:
                env[o] = t


def walk_jaxpr(closed_jaxpr, seeds: Sequence[Optional[Taint]]) -> WalkResult:
    """Walk a ClosedJaxpr with per-invar taint seeds.

    Returns per-class byte buckets for ONE call of the jaxpr, the
    pallas sites encountered, and any walker gaps.  Fresh (non-inplace)
    cache outvars are billed by the caller (:mod:`.traffic`), which
    knows the output pytree's leaf names.
    """
    w = _Walker()
    env: Dict = {}
    jaxpr = closed_jaxpr.jaxpr
    for var, t in zip(jaxpr.invars, seeds):
        if t is not None:
            env[var] = t
    w.walk(jaxpr, env, 1)
    # expose final env so traffic can bill fresh cache outvars
    res = WalkResult(buckets=w.buckets, pallas_sites=w.sites,
                     problems=w.problems)
    res.outvar_taints = tuple(w._get(env, v) for v in jaxpr.outvars)
    return res
