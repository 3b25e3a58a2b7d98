"""Pallas TPU kernel: refresh-window row-state update.

The RTC simulator advances millions of DRAM-row ages per retention
window; on TPU this is the hot inner loop of large-module, long-horizon
sweeps (Fig. 12 runs 4M-row modules over thousands of windows).  The
kernel tiles the row axis into VMEM blocks, computes the wrapped
access-interval membership *inside* the kernel (so only the 8 scalar
policy parameters travel to SMEM, not three O(n_rows) masks), fuses the
age update with the per-block implicit/explicit/violation reductions,
and writes one partial-count triple per grid step.

Block size 8×128 lanes (int32) keeps the working set at
3 * 4 KiB * BLOCK_ROWS/1024 << VMEM and the lane dimension
hardware-aligned (multiples of 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_interpret

__all__ = ["window_update_masked_pallas", "window_update_pallas",
           "BLOCK_ROWS"]

BLOCK_ROWS = 8 * 1024  # int32 rows per VMEM block: 32 KiB in, 32 KiB out


def _kernel(scalars_ref, age_ref, age_out_ref, counts_ref):
    """One row-block of the window update.

    scalars_ref: SMEM int32[8]:
      [acc_start, acc_len, alloc_lo, alloc_hi, ref_lo, ref_hi,
       skip_accessed, base_row_of_block0]
    age_ref / age_out_ref: VMEM int32[BLOCK]
    counts_ref: VMEM int32[3] per block: (implicit, explicit, violation)
    """
    blk = pl.program_id(0)
    acc_start = scalars_ref[0]
    acc_len = scalars_ref[1]
    alloc_lo = scalars_ref[2]
    alloc_hi = scalars_ref[3]
    ref_lo = scalars_ref[4]
    ref_hi = scalars_ref[5]
    skip_accessed = scalars_ref[6]
    base = scalars_ref[7]

    n = age_ref.shape[0]
    row_ids = base + blk * n + jax.lax.iota(jnp.int32, n)
    age = age_ref[...]

    alloc_span = jnp.maximum(alloc_hi - alloc_lo, 1)
    rel = row_ids - alloc_lo
    in_alloc = (row_ids >= alloc_lo) & (row_ids < alloc_hi)
    # Wrapped interval membership: distance from cursor, modulo region.
    off = jnp.mod(rel - jnp.mod(acc_start - alloc_lo, alloc_span), alloc_span)
    accessed = in_alloc & (off < acc_len)

    in_ref = (row_ids >= ref_lo) & (row_ids < ref_hi)
    explicit = in_ref & jnp.where(skip_accessed > 0, ~accessed, True)

    replenished = accessed | explicit
    new_age = jnp.where(replenished, 0, age + 1)
    violation = in_alloc & (new_age > 1)

    age_out_ref[...] = new_age
    counts_ref[0] = jnp.sum(accessed.astype(jnp.int32))
    counts_ref[1] = jnp.sum(explicit.astype(jnp.int32))
    counts_ref[2] = jnp.sum(violation.astype(jnp.int32))


@jax.jit
def window_update_pallas(
    age: jnp.ndarray,
    acc_start,
    acc_len,
    alloc_lo,
    alloc_hi,
    ref_lo,
    ref_hi,
    skip_accessed,
):
    """Tiled window update. Returns (new_age, implicit, explicit, violations).

    ``age`` length must be a multiple of BLOCK_ROWS (callers pad; padded
    rows sit outside [alloc_lo, alloc_hi) and [ref_lo, ref_hi) so they
    contribute nothing).
    """
    n = age.shape[0]
    if n % BLOCK_ROWS:
        raise ValueError(f"row count {n} not a multiple of {BLOCK_ROWS}")
    n_blocks = n // BLOCK_ROWS
    scalars = jnp.stack(
        [
            jnp.asarray(x, jnp.int32)
            for x in (acc_start, acc_len, alloc_lo, alloc_hi, ref_lo, ref_hi,
                      skip_accessed, 0)
        ]
    )
    new_age, counts = pl.pallas_call(
        _kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # scalars broadcast to all blocks
            pl.BlockSpec((BLOCK_ROWS,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS,), lambda i: (i,)),
            pl.BlockSpec((3,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((3 * n_blocks,), jnp.int32),
        ],
        interpret=pallas_interpret(),
    )(scalars, age.astype(jnp.int32))
    counts = counts.reshape(n_blocks, 3).sum(axis=0)
    return new_age, counts[0], counts[1], counts[2]


def _masked_kernel(scalars_ref, age_ref, touched_ref, age_out_ref,
                   counts_ref):
    """One row-block of the trace-driven window update.

    Same state machine as :func:`_kernel`, but the accessed set arrives
    as a per-row VMEM bitmap (one window of a measured trace) instead
    of being computed from the affine cursor scalars — so the scalar
    vector drops the cursor fields:

    scalars_ref: SMEM int32[8]:
      [alloc_lo, alloc_hi, ref_lo, ref_hi, skip_accessed,
       base_row_of_block0, 0, 0]  (padded to match the affine layout)
    age_ref / touched_ref / age_out_ref: VMEM int32[BLOCK]
    counts_ref: VMEM int32[3] per block: (implicit, explicit, violation)
    """
    blk = pl.program_id(0)
    alloc_lo = scalars_ref[0]
    alloc_hi = scalars_ref[1]
    ref_lo = scalars_ref[2]
    ref_hi = scalars_ref[3]
    skip_accessed = scalars_ref[4]
    base = scalars_ref[5]

    n = age_ref.shape[0]
    row_ids = base + blk * n + jax.lax.iota(jnp.int32, n)
    age = age_ref[...]

    in_alloc = (row_ids >= alloc_lo) & (row_ids < alloc_hi)
    accessed = in_alloc & (touched_ref[...] != 0)

    in_ref = (row_ids >= ref_lo) & (row_ids < ref_hi)
    explicit = in_ref & jnp.where(skip_accessed > 0, ~accessed, True)

    replenished = accessed | explicit
    new_age = jnp.where(replenished, 0, age + 1)
    violation = in_alloc & (new_age > 1)

    age_out_ref[...] = new_age
    counts_ref[0] = jnp.sum(accessed.astype(jnp.int32))
    counts_ref[1] = jnp.sum(explicit.astype(jnp.int32))
    counts_ref[2] = jnp.sum(violation.astype(jnp.int32))


@jax.jit
def window_update_masked_pallas(
    age: jnp.ndarray,
    touched: jnp.ndarray,
    alloc_lo,
    alloc_hi,
    ref_lo,
    ref_hi,
    skip_accessed,
):
    """Tiled trace-driven window update.

    Returns (new_age, implicit, explicit, violations).  ``age`` and
    ``touched`` lengths must be an equal multiple of BLOCK_ROWS
    (callers pad; padded rows are untouched and outside every bound).
    """
    n = age.shape[0]
    if n % BLOCK_ROWS:
        raise ValueError(f"row count {n} not a multiple of {BLOCK_ROWS}")
    if touched.shape != age.shape:
        raise ValueError(
            f"touched shape {touched.shape} != age shape {age.shape}")
    n_blocks = n // BLOCK_ROWS
    scalars = jnp.stack(
        [
            jnp.asarray(x, jnp.int32)
            for x in (alloc_lo, alloc_hi, ref_lo, ref_hi, skip_accessed,
                      0, 0, 0)
        ]
    )
    new_age, counts = pl.pallas_call(
        _masked_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # scalars broadcast to all blocks
            pl.BlockSpec((BLOCK_ROWS,), lambda i: (i,)),
            pl.BlockSpec((BLOCK_ROWS,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS,), lambda i: (i,)),
            pl.BlockSpec((3,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((3 * n_blocks,), jnp.int32),
        ],
        interpret=pallas_interpret(),
    )(scalars, age.astype(jnp.int32), touched.astype(jnp.int32))
    counts = counts.reshape(n_blocks, 3).sum(axis=0)
    return new_age, counts[0], counts[1], counts[2]
