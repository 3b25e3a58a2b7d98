"""Pallas TPU kernel: flash attention (GQA, causal, SWA, softcap).

TPU-native tiling of the FlashAttention recurrence:

* ``grid = (batch, q_heads, q_blocks, kv_blocks)`` with the KV axis
  innermost: TPU grids execute sequentially over the last dimension, so
  the online-softmax running state (max, sum, accumulator) lives in
  VMEM scratch across KV steps of one (b, h, q_block) tile;
* BlockSpecs stream one MXU-aligned K/V tile per step HBM->VMEM
  (``kv_block x head_dim``), the GQA group mapping ``ih -> ih // group``
  reading each KV head once per query head in its group;
* causal + sliding-window masks use *block-level early exit*
  (``pl.when`` over the block index) so fully-masked tiles spend no
  MXU cycles — matching the banded FLOP count of the jnp reference;
* sequences that don't tile are padded to the block grid and sliced
  back (padded keys masked in-kernel via ``kv_len``; padded query rows
  discarded), so any (seq, q_block, kv_block) combination lowers;
* fp32 accumulation, bf16/f32 inputs.

VMEM per step: q tile (q_blk*hd*4) + K/V tiles (2*kv_blk*hd*2) +
scores (q_blk*kv_blk*4) + scratch (q_blk*(hd+2)*4) — ~0.8 MiB at the
default 128x512x256 tiling, comfortably inside 16 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_interpret

__all__ = ["flash_attention", "DEFAULT_Q_BLOCK", "DEFAULT_KV_BLOCK"]

DEFAULT_Q_BLOCK = 128
DEFAULT_KV_BLOCK = 512
_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            q_block: int, kv_block: int, n_kv_blocks: int, causal: bool,
            window: Optional[int], softcap: Optional[float],
            kv_len: Optional[int]):
    qb = pl.program_id(2)
    kvb = pl.program_id(3)

    @pl.when(kvb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qb * q_block
    kv_start = kvb * kv_block

    # ---- block-level early exit -------------------------------------------
    live = jnp.asarray(True)
    if causal:
        live &= kv_start <= q_start + q_block - 1
    if window is not None:
        live &= kv_start + kv_block > q_start - window + 1
    if kv_len is not None:
        live &= kv_start < kv_len        # block entirely in tile padding

    @pl.when(live)
    def _step():
        q = q_ref[0, :, 0, :].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        hd = q.shape[-1]
        s = (q @ k.T) * (hd ** -0.5)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        qi = q_start + jax.lax.iota(jnp.int32, q_block)[:, None]
        kj = kv_start + jax.lax.iota(jnp.int32, kv_block)[None, :]
        mask = jnp.ones_like(s, dtype=bool)
        if causal:
            mask &= kj <= qi
        if window is not None:
            mask &= kj > qi - window
        if kv_len is not None:
            mask &= kj < kv_len          # keys in the tile padding
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v

    @pl.when(kvb == n_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, 0, :] = (acc_ref[...] / safe_l[:, None]).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_block", "kv_block"),
)
def flash_attention(
    q: jnp.ndarray,            # [b, sq, h, hd]
    k: jnp.ndarray,            # [b, skv, kvh, hd]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_block: int = DEFAULT_Q_BLOCK,
    kv_block: int = DEFAULT_KV_BLOCK,
) -> jnp.ndarray:
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    if h % kvh:
        raise ValueError("n_heads must be a multiple of n_kv_heads")
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    # Sequences that don't tile are padded up to the block grid and the
    # result sliced back: padded KEYS are masked in-kernel (``kv_len``
    # bounds ``kj`` — causality alone would leave them visible to the
    # padded query rows, and non-causal calls to everyone); padded QUERY
    # rows compute garbage that the final slice discards.
    pad_q = (-sq) % q_block
    pad_kv = (-skv) % kv_block
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_kv
    group = h // kvh
    n_kv = skv_p // kv_block

    kern = functools.partial(
        _kernel, q_block=q_block, kv_block=kv_block, n_kv_blocks=n_kv,
        causal=causal, window=window, softcap=softcap,
        kv_len=skv if pad_kv else None)

    out = pl.pallas_call(
        kern,
        grid=(b, h, sq_p // q_block, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_block, 1, hd),
                         lambda ib, ih, iq, ikv: (ib, iq, ih, 0)),
            pl.BlockSpec((1, kv_block, 1, hd),
                         lambda ib, ih, iq, ikv, g=group: (ib, ikv, ih // g, 0)),
            pl.BlockSpec((1, kv_block, 1, hd),
                         lambda ib, ih, iq, ikv, g=group: (ib, ikv, ih // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, 1, hd),
                               lambda ib, ih, iq, ikv: (ib, iq, ih, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq_p, h, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),      # running max
            pltpu.VMEM((q_block,), jnp.float32),      # running sum
            pltpu.VMEM((q_block, hd), jnp.float32),   # output accumulator
        ],
        interpret=pallas_interpret(),
    )(q, k, v)
    return out[:, :sq] if pad_q else out
