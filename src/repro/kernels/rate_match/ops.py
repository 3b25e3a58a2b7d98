"""Public op: Algorithm-1 schedule bits with backend dispatch."""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.analysis.costs import register_pallas_cost, uniform_cost
from repro.kernels.rate_match.kernel import BLOCK_SLOTS, schedule_pallas
from repro.kernels.rate_match.ref import schedule_block_ref

__all__ = ["schedule_bits", "BLOCK_SLOTS"]

# single-sweep grid: the scalar rate operands stream once, each output
# block is produced once — the uniform cost model is exact
register_pallas_cost("kernels/rate_match/", uniform_cost)


def schedule_bits(
    n_a: int, n_r: int, length: int, *, start: int = 0,
    backend: str = "ref",
):
    """xfer bits for slots [start+1, start+length] (int32 0/1 array).

    Rates are gcd-reduced first so the int32 products ``i * na`` stay
    far from overflow for any module geometry we model.
    """
    g = math.gcd(n_a, n_r) if n_a > 0 else max(n_r, 1)
    na, nr = n_a // g, max(1, n_r // g)
    # Slot index within the repeating period keeps i*na bounded.
    start = start % nr if nr else 0
    if backend == "ref":
        return schedule_block_ref(start, length, na, nr)
    if backend == "pallas":
        pad = (-length) % BLOCK_SLOTS
        bits = schedule_pallas(start, na, nr, length=length + pad)
        return bits[:length]
    raise ValueError(f"unknown backend {backend!r}")
