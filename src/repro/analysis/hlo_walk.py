"""Collective-op walker over partitioned HLO text.

This jaxlib exposes no structured HLO instruction API (``HloModule``
gives ``computations()`` and ``to_string()`` only), so the walker
parses ``compiled.as_text()`` line by line, extracting exactly the
communication instructions GSPMD inserts: ``all-gather``,
``all-reduce``, ``reduce-scatter``, ``all-to-all`` and
``collective-permute`` (plus their ``-start``/``-done`` async split —
a started op is counted once, its ``-done`` is skipped).  Everything
else in the module is device-local and therefore invisible to the
cross-device traffic ledger.

Per collective the walker recovers

* result/operand shapes (dtype + dims, layout annotations stripped),
* the replica grouping, in both the explicit ``{{0,1},{2,3}}`` and the
  iota ``[4,2]<=[2,4]T(1,0)`` form (4 groups of 2),
* jax provenance from the ``metadata`` field (``op_name`` carries the
  eqn path, e.g. ``jit(decode)/.../gather``; ``stack_frame_id`` names
  the innermost frame of the module's ``FileNames`` / ``FileLocations``
  / ``StackFrames`` tables, which resolve to the model source file and
  line), and
* exact wire bytes per device under the standard ring schedules:
  all-gather moves ``out*(g-1)/g`` through every device, reduce-scatter
  ``in*(g-1)/g``, all-reduce ``2*in*(g-1)/g`` (reduce-scatter +
  all-gather), all-to-all ``in*(g-1)/g``, collective-permute ``in``.
  All integer-exact: shard sizes divide by construction.

:func:`classify_collective` then attributes each op to the tensor
family it moves — the page-pool classes (``kv_pool``/``state_pool``)
are the ones the locality lint gates — using dtype (integer collectives
are block-table/length/index ``meta`` traffic) and provenance (the
paged-attention kernel's emulated body, ``models/attention.py`` gather/
scatter sites, the recurrent-state modules, the unembed matmul).
"""
from __future__ import annotations

import dataclasses
import posixpath
import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Shape", "Collective", "parse_collectives",
           "classify_collective", "ledger_rows",
           "COLLECTIVE_KINDS", "POOL_CLASSES", "TENSOR_CLASSES"]

#: canonical collective kinds (async ``-start`` forms fold into these)
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

#: ledger classes whose presence the locality lint gates
POOL_CLASSES = ("kv_pool", "state_pool")

#: full taxonomy a collective can be attributed to
TENSOR_CLASSES = ("kv_pool", "state_pool", "kv", "state", "params",
                  "logits", "meta", "activation", "other")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_INT_DTYPES = frozenset(("pred", "s4", "u4", "s8", "u8", "s16", "u16",
                         "s32", "u32", "s64", "u64"))


@dataclasses.dataclass(frozen=True)
class Shape:
    """One array shape in an HLO type (layout stripped)."""

    dtype: str
    dims: Tuple[int, ...]

    @property
    def size(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n

    @property
    def byte_size(self) -> int:
        return self.size * _DTYPE_BYTES.get(self.dtype, 4)


@dataclasses.dataclass(frozen=True)
class Collective:
    """One GSPMD communication instruction from a partitioned module."""

    kind: str                          # canonical (no -start suffix)
    name: str                          # %all-gather.150
    result_shapes: Tuple[Shape, ...]   # tuple results flattened
    operand_shapes: Tuple[Shape, ...]
    n_groups: int                      # 0 when no replica_groups printed
    group_size: int
    op_name: str = ""
    source_file: str = ""
    source_line: int = 0
    is_async: bool = False

    @property
    def result_bytes(self) -> int:
        if self.is_async and len(self.result_shapes) > 1:
            # async-start results are (operand, result[, contexts]) —
            # the gathered payload is the last array element
            return self.result_shapes[-1].byte_size
        return sum(s.byte_size for s in self.result_shapes)

    @property
    def operand_bytes(self) -> int:
        return sum(s.byte_size for s in self.operand_shapes)

    def wire_bytes_per_device(self) -> int:
        """Exact per-device wire bytes under a ring schedule."""
        g = self.group_size
        if self.kind == "collective-permute":
            return self.operand_bytes
        if g <= 1:
            return 0
        if self.kind == "all-gather":
            return self.result_bytes * (g - 1) // g
        if self.kind == "all-reduce":
            return 2 * self.operand_bytes * (g - 1) // g
        # reduce-scatter / all-to-all
        return self.operand_bytes * (g - 1) // g

    def to_dict(self) -> dict:
        return {
            "kind": self.kind, "name": self.name,
            "result": [f"{s.dtype}{list(s.dims)}" for s in self.result_shapes],
            "operands": [f"{s.dtype}{list(s.dims)}"
                         for s in self.operand_shapes],
            "n_groups": self.n_groups, "group_size": self.group_size,
            "wire_bytes_per_device": self.wire_bytes_per_device(),
            "op_name": self.op_name,
            "source": (f"{self.source_file}:{self.source_line}"
                       if self.source_file else ""),
        }


_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_HEAD_RE = re.compile(
    r"(%[\w.\-]+)\s*=\s*(\([^=]*?\)|[a-z]+\d*\[[\d,]*\](?:\{[^}]*\})?)\s+"
    r"((?:all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?)\(")
_EXPLICIT_GROUPS_RE = re.compile(r"replica_groups=\{(\{[\d,{} ]*\})?\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_TABLE_ROW_RE = re.compile(r"^(\d+) (.*)$")
_FIELD_RE = re.compile(r"(\w+)=(\d+)")


def _stack_frames(hlo_text: str) -> Dict[int, Tuple[str, int]]:
    """Frame id -> (source file, line), from the module's stack-frame
    tables (rows ``<id> "<name>"`` or ``<id> {key=value ...}`` under a
    table header; several blocks merge by id)."""
    rows: Dict[str, Dict[int, str]] = {t: {} for t in _TABLES}
    table = None
    for line in hlo_text.splitlines():
        if line in _TABLES:
            table = line
            continue
        m = _TABLE_ROW_RE.match(line) if table else None
        if m is None:
            table = None
            continue
        rows[table][int(m.group(1))] = m.group(2)
    def fields(v: str) -> Dict[str, int]:
        return {k: int(n) for k, n in _FIELD_RE.findall(v)}

    files = {i: v.strip('"') for i, v in rows["FileNames"].items()}
    locs = {i: fields(v) for i, v in rows["FileLocations"].items()}
    out: Dict[int, Tuple[str, int]] = {}
    for i, v in rows["StackFrames"].items():
        loc = locs.get(fields(v).get("file_location_id"), {})
        out[i] = (files.get(loc.get("file_name_id"), ""), loc.get("line", 0))
    return out


def _parse_shapes(text: str) -> Tuple[Shape, ...]:
    return tuple(Shape(m.group(1),
                       tuple(int(d) for d in m.group(2).split(",") if d))
                 for m in _SHAPE_RE.finditer(text))


def _operand_region(line: str, start: int) -> str:
    """The text inside the collective's argument parens (layouts use
    braces, so only ``T(1,0)``-style parens nest — a depth scan is
    exact)."""
    depth = 0
    for i in range(start, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i]
    return line[start + 1:]


def _parse_groups(line: str, n_devices: Optional[int]) -> Tuple[int, int]:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(1)), int(m.group(2))
    m = _EXPLICIT_GROUPS_RE.search(line)
    if m:
        inner = m.group(1)
        if not inner:
            # replica_groups={}: one group over every participant
            return (1, n_devices or 0)
        groups = re.findall(r"\{([\d, ]*)\}", inner)
        sizes = [len([t for t in g.split(",") if t.strip()]) for g in groups]
        return len(groups), max(sizes) if sizes else 0
    return 0, 0


def parse_collectives(hlo_text: str,
                      n_devices: Optional[int] = None) -> List[Collective]:
    """Every communication instruction in a partitioned HLO module.

    ``n_devices`` resolves the empty ``replica_groups={}`` form (one
    group spanning all participants).  ``-done`` instructions are
    skipped — their ``-start`` carries the shapes and metadata.
    """
    frames = _stack_frames(hlo_text)
    out: List[Collective] = []
    for line in hlo_text.splitlines():
        # ``-done`` ops never match _HEAD_RE (the kind must be followed
        # directly by its open paren), so start/done pairs count once
        m = _HEAD_RE.search(line)
        if m is None:
            continue
        name, result_txt, kind = m.group(1), m.group(2), m.group(3)
        is_async = kind.endswith("-start")
        canonical = kind[:-len("-start")] if is_async else kind
        operands = _operand_region(line, line.index("(", m.end(3)))
        n_groups, group_size = _parse_groups(line, n_devices)
        frame = _FRAME_ID_RE.search(line)
        src, ln = frames.get(int(frame.group(1)), ("", 0)) if frame \
            else ("", 0)
        opn = _OP_NAME_RE.search(line)
        out.append(Collective(
            kind=canonical, name=name,
            result_shapes=_parse_shapes(result_txt),
            operand_shapes=_parse_shapes(operands),
            n_groups=n_groups, group_size=group_size,
            op_name=opn.group(1) if opn else "",
            source_file=src, source_line=ln,
            is_async=is_async))
    return out


# ------------------------------------------------------------ classification
#: model source files that own each cache family.  Paged engines route
#: these sites at pool buffers; contiguous engines at the [B, L, ...]
#: cache — the mode picks which class the site's traffic lands in.
_KV_SOURCES = ("attention.py",)
_STATE_SOURCES = ("rglru.py", "ssm.py")
_PARAM_SOURCES = ("layers.py", "moe.py", "frontends.py")


def classify_collective(c: Collective, mode: str,
                        pool_dims: Optional[Dict[Tuple[int, ...], str]]
                        = None) -> str:
    """Attribute a collective to the tensor family it moves.

    ``mode`` is the *artifact's cache layout* (``contiguous`` /
    ``gather`` / ``pallas_paged``): the same attention/state source
    sites address page pools in paged modes and the contiguous cache
    otherwise (prefill always materializes a contiguous cache, so its
    caller passes ``contiguous`` regardless of the engine backend).
    Integer collectives are ``meta`` (block tables, lengths, scatter
    indices) regardless of site — O(pages) indirection noise, never
    payload.

    ``pool_dims`` maps known pool-buffer shapes (dims tuples) to their
    pool class: a collective whose operand or result *is* a pool buffer
    is classified as that pool even without provenance metadata, so a
    full-pool materialization can never hide behind a missing
    ``op_name``.  Float collectives with no source metadata at all are
    GSPMD reshards of unnamed intermediates — ``activation``.
    """
    shapes = tuple(c.operand_shapes) + tuple(c.result_shapes)
    if shapes and all(s.dtype in _INT_DTYPES for s in shapes):
        return "meta"
    if pool_dims:
        for s in shapes:
            cls = pool_dims.get(s.dims)
            if cls is not None:
                return cls
    paged = mode != "contiguous"
    base = posixpath.basename(c.source_file.replace("\\", "/"))
    if "paged_decode_attention" in c.op_name or "/kernels/" in c.source_file:
        return "kv_pool"
    if "unembed" in c.op_name or "lm_head" in c.op_name:
        return "logits"
    if base in _KV_SOURCES:
        return "kv_pool" if paged else "kv"
    if base in _STATE_SOURCES:
        return "state_pool" if paged else "state"
    if base == "transformer.py" and (
            "dynamic_update_slice" in c.op_name or "scatter" in c.op_name):
        # the stacked-layer cache write site (scan body DUS into the
        # per-layer cache stack) — cache payload, not parameters
        return "kv_pool" if paged else "kv"
    if base in _PARAM_SOURCES or base == "transformer.py":
        return "params"
    if not c.source_file and not c.op_name:
        return "activation"
    return "other"


def ledger_rows(collectives: Sequence[Collective], mode: str,
                pool_dims: Optional[Dict[Tuple[int, ...], str]] = None
                ) -> List[dict]:
    """Aggregate a module's collectives into ledger rows, one per
    (kind, class, source site): instruction count, total wire bytes per
    device, and one representative provenance string."""
    agg: Dict[Tuple[str, str, str], dict] = {}
    for c in collectives:
        cls = classify_collective(c, mode, pool_dims)
        if "paged_decode_attention" in c.op_name:
            site = "kernels/paged_attention"
        else:
            site = posixpath.basename(c.source_file.replace("\\", "/")) \
                or "unattributed"
        row = agg.setdefault((c.kind, cls, site), {
            "kind": c.kind, "class": cls, "site": site,
            "count": 0, "wire_bytes_per_device": 0,
            "op_name": c.op_name,
            "source": (f"{c.source_file}:{c.source_line}"
                       if c.source_file else "")})
        row["count"] += 1
        row["wire_bytes_per_device"] += c.wire_bytes_per_device()
    return [agg[k] for k in sorted(agg)]
