"""Static placement: ShardingPolicy + parameter/batch PartitionSpecs.

``param_specs`` is a pure map over parameter-tree *paths and shapes*
(it runs happily on ``jax.eval_shape`` output), so the placement of a
100B-parameter model is decided without allocating a byte.  The rule
table lives in the package docstring (:mod:`repro.dist`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

__all__ = ["ShardingPolicy", "param_specs", "batch_specs",
           "as_concrete_mesh"]

Axes = Union[None, str, Tuple[str, ...]]


def _mesh_axis_sizes(mesh) -> Tuple[Tuple[str, int], ...]:
    """(axis, size) pairs for a ``Mesh`` OR an ``AbstractMesh`` — the
    abstract form has no device array, only ``shape_tuple``."""
    shape_tuple = getattr(mesh, "shape_tuple", None)
    if shape_tuple is not None:
        return tuple((str(a), int(s)) for a, s in shape_tuple)
    return tuple(zip((str(a) for a in mesh.axis_names),
                     (int(s) for s in mesh.devices.shape)))


def as_concrete_mesh(mesh, devices=None) -> Mesh:
    """Bind an ``AbstractMesh`` description to this process's devices.

    This jax version cannot lower a computation whose shardings name an
    ``AbstractMesh`` (its ``_device_assignment`` is unimplemented), so
    dry-run partitioning binds the abstract description to compile-only
    devices — typically host CPU devices forced into existence with
    ``--xla_force_host_platform_device_count=N`` *before* jax
    initializes (``python -m repro.analysis --mesh N`` does this).
    A concrete ``Mesh`` passes through untouched.
    """
    if isinstance(mesh, Mesh):
        return mesh
    items = _mesh_axis_sizes(mesh)
    n = 1
    for _, s in items:
        n *= s
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < n:
        raise ValueError(
            f"cannot bind abstract mesh {dict(items)} ({n} devices) to "
            f"{len(devices)} available device(s); force host devices "
            f"before jax initializes, e.g. XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    arr = np.array(devices[:n]).reshape([s for _, s in items])
    return Mesh(arr, tuple(a for a, _ in items))


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Names the mesh axes and the sharding regime for one launch.

    ``mesh_axis_sizes`` carries the mesh extents so shape-dependent
    rules (MoE expert-parallel vs tensor-parallel, FSDP divisibility)
    can be decided without a live mesh.  An empty tuple means "sizes
    unknown": the MoE expert-parallel check passes optimistically
    (a wrong guess only costs efficiency), but FSDP/ZeRO-1 scatter is
    SKIPPED — pjit argument shardings do not pad, so a data-axis shard
    is only placed on a provably divisible dim.  Build policies with
    :meth:`for_mesh` to get both.
    """

    mesh_axis_sizes: Tuple[Tuple[str, int], ...] = ()
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    seq_axis: Axes = None
    fsdp: bool = False
    zero1: bool = False
    # FSDP/ZeRO-1 only scatter tensors with at least this many elements
    # — sharding small norms/biases buys nothing and costs a gather.
    fsdp_min_size: int = 1 << 20

    @classmethod
    def for_mesh(cls, mesh, *, seq_axis: Axes = None,
                 fsdp: bool = False, zero1: bool = False,
                 **overrides) -> "ShardingPolicy":
        """Policy for a ``Mesh`` or an ``AbstractMesh`` — the policy
        only consumes axis names and extents, so an abstract mesh
        description (no devices) decides placement identically."""
        sizes = _mesh_axis_sizes(mesh)
        names = tuple(a for a, _ in sizes)
        data = tuple(a for a in names if a in ("pod", "data")) or names[:1]
        model = "model" if "model" in names else names[-1]
        return cls(mesh_axis_sizes=sizes, data_axes=data, model_axis=model,
                   seq_axis=seq_axis, fsdp=fsdp, zero1=zero1, **overrides)

    # ---- axis arithmetic ---------------------------------------------------
    @property
    def batch_spec(self) -> Axes:
        """PartitionSpec entry for a batch dimension."""
        if len(self.data_axes) == 1:
            return self.data_axes[0]
        return tuple(self.data_axes)

    def axis_size(self, name: str) -> Optional[int]:
        return dict(self.mesh_axis_sizes).get(name)

    @property
    def model_size(self) -> Optional[int]:
        return self.axis_size(self.model_axis)

    @property
    def data_size(self) -> Optional[int]:
        n = 1
        for a in self.data_axes:
            s = self.axis_size(a)
            if s is None:
                return None
            n *= s
        return n

    # ---- paged-cache placement --------------------------------------------
    def page_spec(self, n_pages: int) -> Axes:
        """PartitionSpec entry for the page dimension of a paged-cache
        pool (``[n_pages, page_size, ...]``).

        A page pool has no batch dimension — the page dim *is* the
        capacity dim, so it takes the data axes the contiguous cache put
        on batch.  pjit argument shardings do not pad, so the dim is
        only sharded when provably divisible (mirrors the FSDP rule);
        GSPMD then turns the block-table gather into the cross-device
        page fetch.  Unknown mesh sizes or indivisible pools replicate,
        which always lowers.

        This spec is the *signature* placement of the decode step
        regardless of its attention backend: the gather path's
        block-table indexing partitions natively, while the
        ``pallas_paged`` kernel (an opaque call with no GSPMD
        partitioning rule) has its operands gathered/re-sharded around
        the call — the pool still lives sharded between steps, so page
        residency and donation behave identically on real meshes
        (mesh==solo pinned in ``tests/test_serve_multidevice.py``).
        """
        dsize = self.data_size
        if dsize and dsize > 1 and n_pages % dsize == 0:
            return self.batch_spec
        return None

    def slot_spec(self, n_slots: int) -> Axes:
        """PartitionSpec entry for the *slot* dimension of a paged-cache
        block table (``[n_slots, ...]``).

        Block tables ride the data axes with their slots: under the
        device-local decode layout (:func:`page_spec` pools +
        ``shard_map`` in :func:`repro.serve.engine.build_decode_step`)
        each device holds exactly the table rows of the slots pinned to
        its pool extent, so the decode step needs no block-table
        collective either.  Same divisibility rule as :func:`page_spec`:
        indivisible slot counts replicate, which always lowers.
        """
        dsize = self.data_size
        if dsize and dsize > 1 and n_slots % dsize == 0:
            return self.batch_spec
        return None

    def decode_shards(self, max_batch: int, resident_pages: Optional[int],
                      state_pages: Optional[int]) -> int:
        """Number of device-local pool extents a paged serve cache should
        be built with on this policy's mesh: the data-axis extent when
        slots and both pool sizes split evenly across it (the
        ``shard_map`` decode layout), else 1 (single-pool layout — the
        decode step then falls back to GSPMD, which lowers everywhere
        but gathers the pools).  ``None`` pool sizes are engine defaults
        sized per-slot, hence always divisible when ``max_batch`` is."""
        dsize = self.data_size
        if not dsize or dsize <= 1:
            return 1
        if max_batch % dsize:
            return 1
        if resident_pages is not None and resident_pages % dsize:
            return 1
        if state_pages is not None and state_pages % dsize:
            return 1
        return dsize


#: narrowest slice of an expert's width that ``param_specs`` splits
#: experts into: 1024 columns (eight 128-lane tiles), so a grouped
#: matmul still streams wide weight tiles from each slice
EXPERT_SLICE = 1024


def _key(entry) -> str:
    """Stringify one pytree path entry (DictKey/SequenceKey/GetAttrKey)."""
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _add_fsdp(spec: P, shape: Tuple[int, ...], policy: ShardingPolicy,
              skip_dim0: bool = True) -> P:
    """Shard one free, data-divisible dim of a large tensor over the
    data axes.  ``skip_dim0`` protects the stacked group (scan) dim of
    block parameters; ZeRO-1 passes False for flat optimizer moments."""
    n = 1
    for s in shape:
        n *= int(s)
    if n < policy.fsdp_min_size:
        return spec
    dsize = policy.data_size
    if not dsize:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = {a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    if any(a in used for a in policy.data_axes):
        return spec
    for dim in range(1 if skip_dim0 else 0, len(shape)):
        if entries[dim] is None and shape[dim] % dsize == 0:
            entries[dim] = policy.batch_spec
            return P(*entries)
    return spec


def param_specs(shapes, policy: Optional[ShardingPolicy] = None):
    """PartitionSpec pytree for a TransformerLM parameter (shape) tree.

    See the rule table in the :mod:`repro.dist` docstring.  Parameters
    under ``"blocks"`` are stacked over scan groups and keep their
    leading dim unsharded; ``"tail"`` layers are unstacked.
    """
    policy = policy or ShardingPolicy()
    m = policy.model_axis

    def one(path, leaf):
        keys = [_key(e) for e in path]
        top, name = keys[0], keys[-1]
        mod = keys[-2] if len(keys) >= 2 else ""
        nd = len(leaf.shape)
        lead = (None,) if top == "blocks" else ()
        spec = None
        if top == "embed":                       # tok [V, d]
            spec = P(m, None)
        elif top == "lm_head":                   # [d, V]
            spec = P(None, m)
        elif mod == "attn":
            if name in ("wq", "wk", "wv"):       # [d, heads*hd]
                spec = P(*lead, None, m)
            elif name == "wo":                   # [heads*hd, d]
                spec = P(*lead, m, None)
            elif name in ("bq", "bk", "bv"):     # [heads*hd]
                spec = P(*lead, m)
        elif mod == "mlp":
            if name in ("wi", "wg"):             # [d, ff]
                spec = P(*lead, None, m)
            elif name == "wo":                   # [ff, d]
                spec = P(*lead, m, None)
        elif mod == "moe":
            if name in ("wi", "wg", "wo"):       # [E, d, f] / [E, f, d]
                n_storage_experts = leaf.shape[len(lead)]
                width = leaf.shape[len(lead) + (1 if name == "wo" else 2)]
                msize = policy.model_size
                # split every expert's width where the slices stay at
                # least EXPERT_SLICE wide: each device then holds a share
                # of every expert and does the same work whatever the
                # routing; across whole experts otherwise
                width_split = (msize is not None and width % msize == 0
                               and width // msize >= EXPERT_SLICE)
                expert_parallel = not width_split and (
                    msize is None or n_storage_experts % msize == 0)
                if expert_parallel:
                    spec = P(*lead, m, None, None)
                elif name == "wo":
                    spec = P(*lead, None, m, None)
                else:
                    spec = P(*lead, None, None, m)
        elif mod == "ssm":
            if name == "in_proj":                # [d, 2*di]
                spec = P(*lead, None, m)
            elif name == "out_proj":             # [di, d]
                spec = P(*lead, m, None)
        elif mod == "rec":
            if name in ("wx", "wgate", "w_a", "w_i"):   # [d|dl, dl]
                spec = P(*lead, None, m)
            elif name == "out_proj":             # [dl, d]
                spec = P(*lead, m, None)
        if spec is None:
            spec = P(*([None] * nd))
        if policy.fsdp:
            spec = _add_fsdp(spec, tuple(leaf.shape), policy,
                             skip_dim0=(top == "blocks"))
        return spec

    return jax.tree_util.tree_map_with_path(one, shapes)


def batch_specs(policy: ShardingPolicy) -> Tuple[P, P]:
    """(token_spec, label_spec) for [batch, seq] training inputs."""
    spec = P(policy.batch_spec, policy.seq_axis)
    return spec, spec
