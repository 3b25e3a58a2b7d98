"""Mixture-of-Experts FFN: top-k routing, group-wise capacity dispatch.

Dispatch is **group-wise per batch element**: each sequence ranks its
own tokens into per-expert capacity slices and scatters into its own
buffer row.  Every scatter/gather is then local to the (data-sharded)
batch dimension — GSPMD never has to all-reduce a dispatch buffer (the
naive global scatter materialized full multi-GiB expert buffers per
device on the 100B MoE train cells).  Expert compute is a batched
einsum ``becd,edf->becf`` whose b (data) and e (model) dims are plain
batch dims, so the sharding survives the backward pass cleanly.

Capacity: per sequence, ``max(1, cf * k * seq / e)``; overflow tokens
within a sequence drop (standard dropping MoE; decode's seq=1 never
drops since each virtual expert receives at most one routing slot).

Virtual expert split (``cfg.moe_virtual_split = s``): each expert is
stored/computed as ``s`` experts of width ``d_ff/s`` — exact for gated
MLPs (f-slices independent through the activation, wo row-blocks sum)
and chosen so the expert count divides the production model axis
(mixtral: 8 x 2 -> 16).  A Switch-style load-balance aux loss is
computed on the real experts.

Serving (:func:`moe_held`) is dropless and computes only routed rows:
each token's rows (one per chosen virtual expert) are sorted by expert
and run through the grouped matmul kernel
(:mod:`repro.kernels.grouped_matmul`), which streams each held
expert's weights once a call and skips experts with no rows.
Under a manual model axis (:func:`repro.dist.axisenv.model_shard`) a
device holds a share of the layer, read off its weights' shapes: whole
experts (fewer storage experts than the layer has: the ones at
``shard index x held``) or a slice of every expert's width.  It routes
every token over all experts with the replicated router, computes its
share's part for the rows routed to it, and the parts are summed over
the axis.  Under a GSPMD-placed model axis the partitioner cannot split
the kernel, so the layer keeps the batched einsum dispatch of
:func:`moe_apply` there, at a dropless capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.axisenv import (constrain, current_env, model_shard,
                                reduce_model)
from repro.models.config import ModelConfig
from repro.models.layers import dense_init

__all__ = ["moe_init", "moe_apply", "moe_held", "moe_share", "route"]


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    s = cfg.moe_virtual_split
    if f % s:
        raise ValueError("d_ff must divide moe_virtual_split")
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "wi": dense_init(ks[1], (e * s, d, f // s), dtype),
        "wo": dense_init(ks[2], (e * s, f // s, d), dtype),
    }
    if cfg.mlp_gated:
        p["wg"] = dense_init(ks[3], (e * s, d, f // s), dtype)
    return p


def moe_apply(params, cfg: ModelConfig, x, capacity: int | None = None,
              token_mask=None):
    """x: [b, seq, d] -> (y: [b, seq, d], aux_loss: scalar f32).

    ``capacity`` overrides the per-(virtual-)expert slot count.  Pass
    ``seq`` for *dropless* dispatch (each expert can absorb every token
    of the sequence): serving prefill must match the decode path, which
    never drops — capacity-dropping is a train-time regularizer, not an
    inference semantic.

    ``token_mask`` ([b, seq] bool): False (padded) tokens are excluded
    from dispatch entirely — they claim no expert rank and scatter to
    the discard slot — so per-expert occupancy is computed from *real*
    token counts and a right-padded sequence routes real tokens exactly
    as its unpadded twin would (padding only ever appends to the
    exclusive-cumsum rank order, it never displaces a real token).
    """
    b, seq, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    vs = cfg.moe_virtual_split

    probs, gate_vals, expert_idx = route(params, cfg, x)
    aux = _aux_loss(probs.reshape(-1, e), expert_idx.reshape(-1, k), e)

    # --- virtual expert split (layout-only; see module docstring) --------
    if vs > 1:
        e = e * vs
        k = k * vs
        expert_idx = (expert_idx[..., None] * vs
                      + jnp.arange(vs)[None, None, None, :]
                      ).reshape(b, seq, k)
        gate_vals = jnp.repeat(gate_vals, vs, axis=-1)

    if capacity is None:
        capacity = max(1, int(cfg.moe_capacity_factor * k * seq / e)) \
            if seq > 1 else k
    nk = seq * k

    # --- per-sequence rank within expert ---------------------------------
    flat_idx = expert_idx.reshape(b, nk)
    onehot = jax.nn.one_hot(flat_idx, e, dtype=jnp.int32)        # [b,nk,e]
    if token_mask is not None:
        # [b, seq] -> [b, nk]: token t owns flat entries t*k .. t*k+k-1
        mflat = jnp.repeat(token_mask, k, axis=1)
        onehot = onehot * mflat[..., None].astype(jnp.int32)
    ranks = jnp.cumsum(onehot, axis=1) - onehot                  # exclusive
    pos = jnp.sum(ranks * onehot, axis=-1)                       # [b,nk]
    keep = pos < capacity
    if token_mask is not None:
        keep = keep & mflat
    slot = jnp.where(keep, flat_idx * capacity + pos, e * capacity)

    # --- dispatch: local scatter per batch element --------------------------
    src = jnp.broadcast_to(x[:, :, None, :], (b, seq, k, d)
                           ).reshape(b, nk, d)

    def scatter_one(src_b, slot_b):
        buf = jnp.zeros((e * capacity + 1, d), x.dtype)
        return buf.at[slot_b].add(src_b)

    buf = jax.vmap(scatter_one)(src, slot)[:, :-1, :]            # [b,e*c,d]
    xin = constrain(buf.reshape(b, e, capacity, d),
                    "B", _etag(e), None, None)

    # --- expert computation (b, e are batch dims: stays local) -------------
    h = constrain(jnp.einsum("becd,edf->becf", xin, params["wi"]),
                  "B", _etag(e), None, None)
    if "wg" in params:
        act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.mlp_activation]
        h = act(constrain(jnp.einsum("becd,edf->becf", xin, params["wg"]),
                          "B", _etag(e), None, None)) * h
    else:
        h = jax.nn.silu(h)
    yout = jnp.einsum("becf,efd->becd", h, params["wo"])
    yout = constrain(yout, "B", _etag(e), None, None)
    yout = yout.reshape(b, e * capacity, d)

    # --- combine: local gather per batch element, gate-weighted -------------
    zero_row = jnp.zeros((b, 1, d), yout.dtype)
    yext = jnp.concatenate([yout, zero_row], axis=1)
    gathered = jnp.take_along_axis(
        yext, slot[..., None].astype(jnp.int32), axis=1)         # [b,nk,d]
    gathered = constrain(gathered, "B", None, None)
    w = (gate_vals.reshape(b, nk) * keep).astype(gathered.dtype)
    y = jnp.sum(gathered.reshape(b, seq, k, d)
                * w.reshape(b, seq, k)[..., None], axis=2)
    return y, aux


def route(params, cfg: ModelConfig, x):
    """Top-k routing over every expert: ``(probs [..., e] f32, gates
    [..., k] renormalised to sum to one, expert ids [..., k])``."""
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    return probs, gate_vals, expert_idx


def moe_held(params, cfg: ModelConfig, x, token_mask=None, layer=None):
    """Dropless serving expert layer.  x: [b, seq, d] -> (y [b, seq, d],
    rows [n_experts] int32: the rows routed to each expert, masked
    tokens excluded).

    ``token_mask`` ([b, seq] bool): False (padded) tokens route nowhere
    and add nothing.  ``layer``: ``wi``/``wg``/``wo`` are the stack of
    every layer's weights (``[layers, E*vs, ...]``) and this is layer
    ``layer`` of it; the kernel reads the stack where it lies.  See the
    module docstring for the share a device holds under a manual model
    axis: the layer is :func:`moe_share` summed over that axis."""
    part, rows = moe_share(params, cfg, x, token_mask, layer)
    return reduce_model(part).astype(x.dtype), rows


def moe_share(params, cfg: ModelConfig, x, token_mask=None, layer=None):
    """The part of :func:`moe_held`'s output that this device's share of
    the experts gives (the whole output without a manual model axis),
    in float32, before the sum over the axis; same arguments and
    ``rows``.  Expert outputs are weighted and combined in float32."""
    b, seq, d = x.shape
    e, k, vs = cfg.n_experts, cfg.experts_per_token, cfg.moe_virtual_split
    _, gates, idx = route(params, cfg, x)
    valid = (jnp.ones((b, seq), bool) if token_mask is None
             else token_mask)
    rows = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.int32)
                   * valid[..., None, None].astype(jnp.int32),
                   axis=(0, 1, 2))
    experts = {n: params[n] for n in ("wi", "wg", "wo") if n in params}
    if layer is None:
        experts = {n: w[None] for n, w in experts.items()}
        layer = 0
    env = current_env()
    msize = env.size("M") if env is not None else None
    if model_shard() is None and msize and msize > 1:
        one = dict(params, **{n: w[layer] for n, w in experts.items()})
        y, _ = moe_apply(one, cfg, x, capacity=seq if seq > 1 else None,
                         token_mask=token_mask)
        return y.astype(jnp.float32), rows
    from repro.kernels.grouped_matmul.ops import grouped_matmul

    # one row per (token, chosen virtual expert), sorted by expert;
    # masked tokens take the id past the last expert and sort last
    ev, kv, t = e * vs, k * vs, b * seq
    vidx = (idx[..., None] * vs + jnp.arange(vs)).reshape(t, kv)
    flat = jnp.where(valid.reshape(t, 1), vidx, ev).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(flat, ev, dtype=jnp.int32), axis=0)
    held = experts["wi"].shape[1]
    shard = model_shard()
    offset = (jax.lax.axis_index(shard[0]) * held
              if shard is not None and held < ev else 0)

    def gmm(lhs, w, out_dtype=None):
        return grouped_matmul(lhs, w, sizes, offset, layer,
                              out_dtype=out_dtype)

    lhs = x.reshape(t, d)[order // kv]
    h = gmm(lhs, experts["wi"])
    if "wg" in experts:
        act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.mlp_activation]
        h = act(gmm(lhs, experts["wg"])) * h
    else:
        h = jax.nn.silu(h)
    out = gmm(h, experts["wo"], jnp.float32)                    # [t*kv, d]
    # the kernel writes this share's rows only: keep those
    sid = flat[order]
    mine = (sid >= offset) & (sid < offset + held)
    w = jnp.repeat(gates, vs, axis=-1).reshape(-1)[order]
    part = jnp.where(mine[:, None], out * w[:, None], 0)
    inv = jnp.argsort(order)
    return jnp.sum(part[inv].reshape(b, seq, kv, d), axis=2), rows


def _etag(e):
    env = current_env()
    msize = env.size("M") if env else None
    return "M" if (msize and e % msize == 0) else None


def _aux_loss(probs, expert_idx, e):
    """Switch-style load-balance loss (on the REAL experts)."""
    density = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0
    )
    mean_probs = jnp.mean(probs, axis=0)
    return (e * jnp.sum(density * mean_probs)).astype(jnp.float32)
