"""Public op: refresh-window row-state update with backend dispatch.

``window_update(..., backend=)`` — affine-cursor access model;
``window_update_masked(..., backend=)`` — trace-driven bitmap model:
  * ``"pallas"`` — the tiled TPU kernel (interpreted off the TPU);
  * ``"ref"``    — the pure-jnp oracle (always available, used for
    allclose validation and as the fast path under jit on CPU).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.analysis.costs import register_pallas_cost, uniform_cost
from repro.kernels.refresh_sim.kernel import (
    BLOCK_ROWS, window_update_masked_pallas, window_update_pallas)
from repro.kernels.refresh_sim.ref import (
    window_update_masked_ref, window_update_ref)

__all__ = ["window_update", "window_update_masked", "BLOCK_ROWS"]

# row-tiled single sweep: age rows in, age rows + per-block counts out,
# every block touched exactly once — the uniform cost model is exact
register_pallas_cost("kernels/refresh_sim/", uniform_cost)


def window_update(
    age: jnp.ndarray,
    acc_start,
    acc_len,
    alloc_lo,
    alloc_hi,
    ref_lo,
    ref_hi,
    skip_accessed,
    *,
    backend: str = "ref",
):
    """Returns (new_age, n_implicit, n_explicit, n_violations)."""
    if backend == "pallas":
        n = age.shape[0]
        pad = (-n) % BLOCK_ROWS
        if pad:
            # Padded rows live past every bound: inert.
            age_p = jnp.concatenate([age, jnp.zeros((pad,), age.dtype)])
        else:
            age_p = age
        new_age, imp, exp, vio = window_update_pallas(
            age_p, acc_start, acc_len, alloc_lo, alloc_hi, ref_lo, ref_hi,
            skip_accessed,
        )
        return new_age[:n], imp, exp, vio
    if backend == "ref":
        row_ids = jnp.arange(age.shape[0], dtype=jnp.int32)
        new_age, imp, exp, vio = window_update_ref(
            age, row_ids,
            jnp.asarray(acc_start, jnp.int32), jnp.asarray(acc_len, jnp.int32),
            jnp.asarray(alloc_lo, jnp.int32), jnp.asarray(alloc_hi, jnp.int32),
            jnp.asarray(ref_lo, jnp.int32), jnp.asarray(ref_hi, jnp.int32),
            jnp.asarray(skip_accessed, bool),
        )
        return new_age, imp.sum(), exp.sum(), vio.sum()
    raise ValueError(f"unknown backend {backend!r}")


def window_update_masked(
    age: jnp.ndarray,
    touched: jnp.ndarray,
    alloc_lo,
    alloc_hi,
    ref_lo,
    ref_hi,
    skip_accessed,
    *,
    backend: str = "ref",
):
    """Trace-driven window update (accessed set = per-row bitmap).

    Returns (new_age, n_implicit, n_explicit, n_violations).
    """
    if touched.shape != age.shape:
        raise ValueError(
            f"touched shape {touched.shape} != age shape {age.shape}")
    if backend == "pallas":
        n = age.shape[0]
        pad = (-n) % BLOCK_ROWS
        if pad:
            # Padded rows live past every bound and are untouched: inert.
            age_p = jnp.concatenate([age, jnp.zeros((pad,), age.dtype)])
            touched_p = jnp.concatenate(
                [touched, jnp.zeros((pad,), touched.dtype)])
        else:
            age_p, touched_p = age, touched
        new_age, imp, exp, vio = window_update_masked_pallas(
            age_p, touched_p, alloc_lo, alloc_hi, ref_lo, ref_hi,
            skip_accessed,
        )
        return new_age[:n], imp, exp, vio
    if backend == "ref":
        row_ids = jnp.arange(age.shape[0], dtype=jnp.int32)
        new_age, imp, exp, vio = window_update_masked_ref(
            age, row_ids, touched,
            jnp.asarray(alloc_lo, jnp.int32), jnp.asarray(alloc_hi, jnp.int32),
            jnp.asarray(ref_lo, jnp.int32), jnp.asarray(ref_hi, jnp.int32),
            jnp.asarray(skip_accessed, bool),
        )
        return new_age, imp.sum(), exp.sum(), vio.sum()
    raise ValueError(f"unknown backend {backend!r}")
