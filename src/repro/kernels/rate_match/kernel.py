"""Pallas TPU kernel: bulk Algorithm-1 schedule generation.

Full-RTC's rate FSM emits one xfer bit per refresh slot; sweeping a
4M-row module over many retention windows means generating O(10^8)
schedule bits when replaying traces.  The closed form is embarrassingly
parallel, so the kernel materializes bits in VMEM-sized blocks from
nothing but three SMEM scalars (start, na, nr) — zero HBM input
bandwidth, output-bound by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_interpret

__all__ = ["schedule_pallas", "BLOCK_SLOTS"]

BLOCK_SLOTS = 16 * 1024  # 64 KiB int32 out per block


def _kernel(scalars_ref, out_ref):
    blk = pl.program_id(0)
    start = scalars_ref[0]
    na = scalars_ref[1]
    nr = scalars_ref[2]
    n = out_ref.shape[0]
    i = start + blk * n + 1 + jax.lax.iota(jnp.int32, n)
    cur = (i * na + (nr - 1)) // nr
    prev = ((i - 1) * na + (nr - 1)) // nr
    bits = (cur - prev).astype(jnp.int32)
    out_ref[...] = jnp.where(nr <= na, jnp.ones_like(bits), bits)


@functools.partial(jax.jit, static_argnames=("length",))
def schedule_pallas(start, na, nr, *, length: int):
    """xfer bits for slots [start+1, start+length]; length % BLOCK == 0."""
    if length % BLOCK_SLOTS:
        raise ValueError(f"length {length} not a multiple of {BLOCK_SLOTS}")
    scalars = jnp.stack([jnp.asarray(x, jnp.int32) for x in (start, na, nr)])
    return pl.pallas_call(
        _kernel,
        grid=(length // BLOCK_SLOTS,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((BLOCK_SLOTS,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((length,), jnp.int32),
        interpret=pallas_interpret(),
    )(scalars)
