"""Pure-jnp oracle for the paged decode-attention kernel.

Reproduces the *gather* decode path of ``repro.models.attention`` —
resolve the block-table indirection into the contiguous
``[b, cache_len]`` logical view, then run the one-token grouped-query
attention math on it — with the full feature set the kernel supports:
per-slot absolute positions, ring/append cache semantics (a slot's
valid positions are derived from ``pos`` exactly as
``attention._cache_positions`` does), sliding-window masking, and
attention-logit softcapping.  fp32 softmax accumulation.

This is the bitwise mirror of what ``attn_decode`` computes on a paged
cache with ``backend="gather"``; the Pallas kernel is validated against
it with an interpret-mode accumulation-order tolerance (see
``tests/test_paged_attention_kernel.py``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["paged_decode_ref"]


def paged_decode_ref(
    q: jnp.ndarray,        # [b, kv_heads, group, head_dim] post-RoPE query
    kp: jnp.ndarray,       # [(layers,) n_pages, page_size, kv_heads*head_dim]
    vp: jnp.ndarray,
    block: jnp.ndarray,    # [b, n_logical_pages] int32 pool page ids
    pos: jnp.ndarray,      # [b] int32 absolute position being decoded
    layer=None,            # [] int32 layer of a stacked pool; None if 3-D
    *,
    cache_len: int,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jnp.ndarray:
    """Gather + one-token GQA attention. Returns [b, kv_heads, group, hd]."""
    b, kvh, g, hd = q.shape
    n_lp = block.shape[1]
    if layer is not None:
        kp, vp = kp[layer], vp[layer]
    page_size = kp.shape[1]
    k = kp[block].reshape(b, n_lp * page_size, kvh, hd)[:, :cache_len]
    v = vp[block].reshape(b, n_lp * page_size, kvh, hd)[:, :cache_len]

    # Absolute position held by each ring slot (-1 if never written):
    # slot s holds the newest p <= pos with p % cache_len == s.
    slots = jnp.arange(cache_len)
    kv_pos = pos[:, None] - ((pos[:, None] % cache_len - slots[None])
                             % cache_len)
    valid = kv_pos >= 0
    if window is not None:
        valid &= kv_pos > pos[:, None] - window

    scale = hd ** -0.5
    logits = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)
