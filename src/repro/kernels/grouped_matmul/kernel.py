"""Pallas TPU kernel: grouped matrix multiplication over rows sorted by
group, for the held-expert layer of :mod:`repro.models.moe`.

``lhs`` rows are sorted by group (expert); group ``i`` owns rows
``[sum(sizes[:i]), sum(sizes[:i+1]))``.  Each row is multiplied by its
group's matrix.  Only the groups ``[offset, offset + held)`` are held
here, so a device computes its own experts' rows and nothing else.
It is dropless: no capacity, no padding per group.

* **weights** — ``rhs`` is the layer stack ``[layers, held, k, n]`` and
  the layer index a scalar-prefetch operand: the decode and prefill
  steps pass the stacked expert weights of every layer as they lie, so
  no layer's weights are sliced or copied before the call (a custom
  call's operand is a buffer, so a slice would be a copy of the layer's
  whole expert weights);
* ``grid = (n tiles, active row tiles)``: the row-tile axis walks the
  group metadata of megablox's grouped matmul (``make_group_metadata``:
  which group and which row tile each step works on, row tiles a group
  shares with its neighbours visited once per group, groups with no
  rows not at all), and the weight block's index map resolves
  ``(layer, group - offset, 0, n)``.  A block holds the whole
  contraction, so consecutive steps of one group keep the same weight
  block and each held group's weights stream HBM->VMEM once per call,
  however many row tiles its rows fill: a prefill of many rows a group
  costs about what a decode step's few rows do;
* each step stores the rows of its group, the tile's other rows left as
  they are (a row tile two groups share is stored by each in turn).
  Rows of groups not held here, and rows past the routed ones, are
  left unwritten: callers keep only their own rows.

Row tile 128: with the weights read once, a larger tile only adds the
operations of a shared tile's other groups' rows.  The n tile is the
widest (up to 2048 lanes) whose ``k x tn`` weight block fits
``BLOCK_BYTES``; the lhs is read once per n tile.  VMEM per step: lhs
``tm*k`` and weight ``k*tn`` blocks and the ``tm*tn`` output,
double-buffered, and the fp32 product: about 20 MB at ``k = 6144, tn =
512`` or ``k = 2048, tn = 2048`` in bf16, within the limit the call
sets.  In the device trace and the HLO the
kernel is the instruction ``grouped_matmul``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from repro.kernels import pallas_interpret

__all__ = ["grouped_matmul"]


#: rows per tile
TM = 128
#: most bytes of one ``k x tn`` weight block
BLOCK_BYTES = 8 * 2 ** 20


def _n_tile(k: int, n: int, itemsize: int) -> int:
    """Widest lane multiple up to 2048 that divides ``n`` and keeps a
    ``k x tn`` weight block within :data:`BLOCK_BYTES`; the whole ``n``
    where no lane multiple divides it (small shapes)."""
    t = 2048
    while t >= 128:
        if n % t == 0 and k * t * itemsize <= BLOCK_BYTES:
            return t
        t //= 2
    return 128 if n % 128 == 0 else n


def _kernel(offsets, group_ids, tile_ids, _offset, _layer, lhs_ref, rhs_ref,
            out_ref, *, tm, tn):
    step = pl.program_id(1)
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    g = group_ids[step]
    row = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
           + tile_ids[step] * tm)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, group_offset, layer,
                   out_dtype=None) -> jnp.ndarray:
    """``lhs [m, k]`` (rows sorted by group) times layer ``layer`` of
    the held groups' ``rhs [layers, held, k, n]`` -> ``[m, n]`` in
    ``out_dtype`` (default ``lhs``'s; fp32 accumulation).
    ``group_sizes [G]`` int32 counts every group's rows, held or not;
    ``group_offset`` is the first held group.  Rows other than the held
    groups' are undefined."""
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    m, k = lhs.shape
    _, held, _, n = rhs.shape
    tm = TM
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tn = _n_tile(k, n, rhs.dtype.itemsize)
    offset = jnp.reshape(jnp.asarray(group_offset, jnp.int32), (1,))
    (offsets, group_ids, tile_ids), active = make_group_metadata(
        group_sizes=group_sizes.astype(jnp.int32), m=m + pad, tm=tm,
        start_group=offset[0], num_nonzero_groups=held,
        visit_empty_groups=False)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // tn, active),
        in_specs=[
            pl.BlockSpec((tm, k), lambda n_i, s, o, gi, ti, off, ly:
                         (ti[s], 0)),
            # the group's weights of the one layer, as they lie
            pl.BlockSpec((None, None, k, tn),
                         lambda n_i, s, o, gi, ti, off, ly:
                         (ly[0], gi[s] - off[0], 0, n_i)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda n_i, s, o, gi, ti, off, ly:
                               (ti[s], n_i)),
    )
    vmem = (2 * (tm * k * lhs.dtype.itemsize + k * tn * rhs.dtype.itemsize
                 + tm * tn * out_dtype.itemsize) + 2 * tm * tn * 4)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m + pad, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem + 4 * 2 ** 20, 32 * 2 ** 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(lhs.size * (n // tn) * lhs.dtype.itemsize
                            + held * k * n * rhs.dtype.itemsize
                            + m * n * out_dtype.itemsize)),
        interpret=pallas_interpret(),
    )(offsets, group_ids, tile_ids, offset, layer, lhs, rhs)
    return out[:m]
