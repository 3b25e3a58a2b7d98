"""Unified decoder-only model covering all 10 assigned architectures.

One block-stack implementation, scanned over depth in *pattern groups*
(the repeating unit of ``cfg.attn_pattern`` — e.g. (local, global) for
gemma2-9b, (rglru, rglru, local) for recurrentgemma) so the HLO stays
O(1) in depth while heterogeneous layer schedules remain expressible.

API (functional, dict pytrees):
    model = TransformerLM(cfg)
    params = model.init(key)                      # or jax.eval_shape
    logits, aux = model.apply(params, tokens)     # train / prefill
    loss = model.loss(params, tokens, labels)
    cache = model.init_cache(batch, max_len)
    logits, cache = model.decode_step(params, cache, token, pos)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.axisenv import constrain, model_shard, reduce_model
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (embed_init, mlp_apply, mlp_init, rmsnorm,
                                 rmsnorm_init, softcap)

__all__ = ["TransformerLM"]


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[cfg.dtype]


class TransformerLM:
    """``unroll=True`` replaces the depth ``lax.scan`` with a Python
    loop over groups.  Used by the dry-run analysis pass: XLA's
    HloCostAnalysis visits a while-loop body ONCE regardless of trip
    count, so only the unrolled HLO yields exact per-step FLOPs / bytes
    / collective counts (verified in tests/test_dryrun.py).  The scan
    form keeps compile time O(1) in depth for training/serving and the
    multi-pod compile proof."""

    def __init__(self, cfg: ModelConfig, remat: str = "none",
                 unroll: bool = False):
        if remat not in ("none", "full", "dots"):
            raise ValueError(f"unknown remat policy {remat!r}")
        self.cfg = cfg
        self.remat = remat
        self.unroll = unroll

    # ------------------------------------------------------------------ init
    def _layer_init(self, key, kind: str) -> dict:
        cfg, dt = self.cfg, _dtype(self.cfg)
        ks = jax.random.split(key, 4)
        p = {"ln1": rmsnorm_init(cfg.d_model, dt)}
        if kind in ("global", "local"):
            p["attn"] = attn.attn_init(ks[0], cfg, dt)
            p["ln2"] = rmsnorm_init(cfg.d_model, dt)
            if cfg.n_experts:
                p["moe"] = moe_mod.moe_init(ks[1], cfg, dt)
            else:
                p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                    cfg.mlp_gated, dt)
        elif kind == "ssm":
            p["ssm"] = ssm_mod.ssm_init(ks[0], cfg, dt)
        elif kind == "rglru":
            p["rec"] = rglru_mod.rglru_init(ks[0], cfg, dt)
            p["ln2"] = rmsnorm_init(cfg.d_model, dt)
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                cfg.mlp_gated, dt)
        else:  # pragma: no cover
            raise ValueError(kind)
        return p

    def init(self, key) -> dict:
        cfg, dt = self.cfg, _dtype(self.cfg)
        ke, kb, kh = jax.random.split(key, 3)
        params = {"embed": embed_init(ke, cfg.vocab_size, cfg.d_model, dt)}
        blocks = []
        for pos, kind in enumerate(cfg.attn_pattern):
            per_group = [
                self._layer_init(jax.random.fold_in(kb, g * 31 + pos), kind)
                for g in range(cfg.n_groups)
            ]
            blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group))
        params["blocks"] = tuple(blocks)
        if cfg.pattern_tail:
            params["tail"] = tuple(
                self._layer_init(jax.random.fold_in(kb, 7919 + i), kind)
                for i, kind in enumerate(cfg.pattern_tail)
            )
        params["final_norm"] = rmsnorm_init(cfg.d_model, dt)
        if not cfg.tie_embeddings:
            from repro.models.layers import dense_init
            params["lm_head"] = dense_init(kh, (cfg.d_model, cfg.vocab_size), dt)
        return params

    def abstract_params(self) -> dict:
        """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
        return jax.eval_shape(
            lambda: self.init(jax.random.key(0))
        )

    # ------------------------------------------------------------- embedding
    def _norm(self, p, x):
        return rmsnorm(p, x, self.cfg.rms_norm_eps)

    def _embed(self, params, tokens):
        cfg = self.cfg
        tok = params["embed"]["tok"]
        shard = model_shard()
        if shard is not None and tok.shape[0] < cfg.vocab_size:
            # this device holds one slice of the vocabulary: look up the
            # tokens that fall in it, zeros elsewhere, and sum the slices
            v = tok.shape[0]
            local = tokens - jax.lax.axis_index(shard[0]) * v
            hit = (local >= 0) & (local < v)
            x = reduce_model(jnp.where(hit[..., None],
                                       tok[jnp.clip(local, 0, v - 1)], 0))
        else:
            x = tok[tokens]
        if cfg.scale_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return x

    def _unembed(self, params, x):
        cfg = self.cfg
        x = self._norm(params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["tok"].T
        else:
            logits = x @ params["lm_head"]
        if logits.ndim == 3:
            # vocab-sharded; seq stays sequence-parallel only for real
            # sequences (decode's singleton seq dim must not grab axes)
            stag = "S" if logits.shape[1] > 1 else None
            logits = constrain(logits, "B", stag, "M")
        return softcap(logits.astype(jnp.float32), cfg.logit_softcap)

    # ----------------------------------------------------------- full forward
    def _block_apply(self, kind, p, x, positions):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if kind in ("global", "local"):
            x = x + attn.attn_apply(p["attn"], cfg, self._norm(p["ln1"], x),
                                    positions, kind)
            h = self._norm(p["ln2"], x)
            if cfg.n_experts:
                y, aux = moe_mod.moe_apply(p["moe"], cfg, h)
            else:
                y = mlp_apply(p["mlp"], h, cfg.mlp_activation)
            x = x + y
        elif kind == "ssm":
            x = x + ssm_mod.ssm_apply(p["ssm"], cfg, self._norm(p["ln1"], x))
        elif kind == "rglru":
            x = x + rglru_mod.rglru_apply(p["rec"], cfg,
                                          self._norm(p["ln1"], x))
            x = x + mlp_apply(p["mlp"], self._norm(p["ln2"], x),
                              cfg.mlp_activation)
        return x, aux

    def apply(self, params, tokens=None, embeds=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-sequence forward. Returns (logits f32, aux_loss f32).

        ``embeds`` ([b, s, d]) replaces token embedding for the stub
        modality frontends (vlm/audio input_specs feed precomputed
        patch/frame embeddings, per the assignment).
        """
        x, aux = self.hidden(params, tokens=tokens, embeds=embeds)
        return self._unembed(params, x), aux

    def hidden(self, params, tokens=None, embeds=None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Trunk forward up to (excluding) the unembed.

        Returns (hidden [b, s, d], aux_loss).  Shared by ``apply`` and
        the sequence-chunked CE loss path.
        """
        cfg = self.cfg
        if embeds is not None:
            x = embeds.astype(_dtype(cfg))
        else:
            x = self._embed(params, tokens)
        x = constrain(x, "B", "S", None)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

        def group_body(carry, gp):
            x, aux = carry
            for i, kind in enumerate(cfg.attn_pattern):
                x, a = self._block_apply(kind, gp[i], x, positions)
                x = constrain(x, "B", "S", None)
                aux = aux + a
            return (x, aux), None

        if self.remat != "none":
            policy = (jax.checkpoint_policies.checkpoint_dots
                      if self.remat == "dots" else
                      jax.checkpoint_policies.nothing_saveable)
            group_body = jax.checkpoint(group_body, policy=policy,
                                        prevent_cse=self.unroll)

        carry = (x, jnp.zeros((), jnp.float32))
        if self.unroll:
            for g in range(cfg.n_groups):
                gp = jax.tree.map(lambda l: l[g], params["blocks"])
                carry, _ = group_body(carry, gp)
            x, aux = carry
        else:
            (x, aux), _ = jax.lax.scan(group_body, carry, params["blocks"])
        for i, kind in enumerate(cfg.pattern_tail):
            x, a = self._block_apply(kind, params["tail"][i], x, positions)
            x = constrain(x, "B", "S", None)
            aux = aux + a
        return x, aux

    def loss(self, params, tokens=None, labels=None, embeds=None,
             aux_coeff: float = 0.01) -> jnp.ndarray:
        logits, aux = self.apply(params, tokens=tokens, embeds=embeds)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return jnp.mean(nll) + aux_coeff * aux

    # ----------------------------------------------------------------- decode
    def _one_cache(self, kind, batch, max_len, dt):
        cfg = self.cfg
        if kind in ("global", "local"):
            return attn.init_kv_cache(
                cfg, batch, cfg.decode_cache_len(kind, max_len), dt)
        if kind == "ssm":
            return ssm_mod.init_ssm_cache(cfg, batch, dt)
        if kind == "rglru":
            return rglru_mod.init_rglru_cache(cfg, batch, dt)
        raise ValueError(kind)  # pragma: no cover

    def init_cache(self, batch: int, max_len: int) -> dict:
        """{'groups': per-pattern-position caches stacked over groups,
        'tail': per-tail-layer caches}."""
        cfg, dt = self.cfg, _dtype(self.cfg)
        groups = []
        for kind in cfg.attn_pattern:
            c = self._one_cache(kind, batch, max_len, dt)
            groups.append(
                jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (cfg.n_groups,) + x.shape
                    ),
                    c,
                )
            )
        tail = tuple(self._one_cache(kind, batch, max_len, dt)
                     for kind in cfg.pattern_tail)
        return {"groups": tuple(groups), "tail": tail}

    def _one_paged_cache(self, kind, batch, max_ctx, page_size, kv_pages, dt,
                         state_pages=None, shards=1):
        cfg = self.cfg
        if kind in ("global", "local"):
            return attn.init_paged_kv_cache(
                cfg, batch, cfg.decode_cache_len(kind, max_ctx),
                page_size, kv_pages, dt, shards=shards)
        n_state = (batch + shards * attn.RESERVED_PAGES
                   if state_pages is None else state_pages)
        if kind == "ssm":
            return ssm_mod.init_paged_ssm_cache(cfg, batch, n_state, dt,
                                                shards=shards)
        if kind == "rglru":
            return rglru_mod.init_paged_rglru_cache(cfg, batch, n_state, dt,
                                                    shards=shards)
        raise ValueError(kind)  # pragma: no cover

    def init_paged_cache(self, batch: int, max_ctx: int, page_size: int,
                         kv_pages: int, state_pages=None,
                         shards: int = 1) -> dict:
        """Paged twin of :meth:`init_cache`: the same {'groups', 'tail'}
        structure, but each attention layer holds a ``kv_pages``-page
        pool (incl. the reserved pages) behind a per-slot block table
        sized for ``max_ctx`` logical positions, and each recurrent
        layer a ``state_pages``-deep state-page pool (default: one page
        per slot plus the reserved pages; a larger extent buys the data
        axes a divisible page dim to shard).  ``shards`` splits every
        pool into that many equal per-device extents, each with its own
        reserved ZERO/DUMP pair, and pins slot ``s`` (its dead-slot DUMP
        target) to extent ``s // (batch/shards)`` — the layout
        :func:`repro.serve.engine.build_decode_step` maps device-locally
        under ``shard_map``.  ``decode_step`` accepts either form
        unchanged; a fresh paged cache decodes bit-identically to a
        fresh ``init_cache(batch, max_ctx)`` once pages are assigned
        (see :class:`repro.serve.paging.PageTable`)."""
        cfg, dt = self.cfg, _dtype(self.cfg)
        groups = []
        for kind in cfg.attn_pattern:
            c = self._one_paged_cache(kind, batch, max_ctx, page_size,
                                      kv_pages, dt, state_pages, shards)
            groups.append(
                jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (cfg.n_groups,) + x.shape
                    ),
                    c,
                )
            )
        tail = tuple(self._one_paged_cache(kind, batch, max_ctx, page_size,
                                           kv_pages, dt, state_pages, shards)
                     for kind in cfg.pattern_tail)
        return {"groups": tuple(groups), "tail": tail}

    def _ffn_serve(self, p, h, token_mask=None, experts=None):
        """The serving paths' MLP or dropless expert layer, reduced over
        a manual model axis: ``(y, rows)``, ``rows`` the expert layer's
        rows per expert (``None`` for an MLP).  ``experts``: ``(stack,
        layer)``, the expert weights stacked over layer groups
        (:meth:`_split_experts`) and this layer's group."""
        cfg = self.cfg
        if not cfg.n_experts:
            return (reduce_model(mlp_apply(p["mlp"], h, cfg.mlp_activation)),
                    None)
        if experts is None:
            return moe_mod.moe_held(p["moe"], cfg, h, token_mask=token_mask)
        stack, layer = experts
        return moe_mod.moe_held(dict(p["moe"], **stack), cfg, h,
                                token_mask=token_mask, layer=layer)

    def _split_experts(self, blocks):
        """``(blocks, stacks)``: the stacked block parameters without
        the expert weights, for the depth loop to slice per group, and
        the expert weights of each pattern position stacked over groups
        (``None`` without experts), which the grouped matmul reads where
        they lie: a slice of them would be a copy of the layer's whole
        expert weights, made every step."""
        if not self.cfg.n_experts:
            return blocks, None
        thin, stacks = [], []
        for p in blocks:
            moe = p.get("moe", {})
            thin.append(dict(p, moe={"router": moe["router"]})
                        if moe else p)
            stacks.append({n: w for n, w in moe.items() if n != "router"})
        return tuple(thin), tuple(stacks)

    def _block_prefill(self, kind, p, x, positions, max_len, lengths=None,
                       experts=None):
        """Full-sequence block forward that also emits the decode cache.

        ``lengths`` ([b] int32): right-padded (length-bucketed) prefill —
        each family freezes/ignores padded positions so rows below
        ``length`` and the emitted cache are bit-identical to an
        unpadded forward (see the per-family prefill docstrings).
        """
        cfg = self.cfg
        if kind in ("global", "local"):
            h, c = attn.attn_prefill(p["attn"], cfg, self._norm(p["ln1"], x),
                                     positions, kind,
                                     cfg.decode_cache_len(kind, max_len),
                                     lengths=lengths)
            x = x + h
            # dropless experts: prefill must agree with decode; padded
            # tokens route nowhere
            mask = None if lengths is None else positions < lengths[:, None]
            y, _ = self._ffn_serve(p, self._norm(p["ln2"], x), mask,
                                   experts)
            x = x + y
        elif kind == "ssm":
            h, c = ssm_mod.ssm_prefill(p["ssm"], cfg, self._norm(p["ln1"], x),
                                       lengths=lengths)
            x = x + h
        elif kind == "rglru":
            h, c = rglru_mod.rglru_prefill(p["rec"], cfg,
                                           self._norm(p["ln1"], x),
                                           lengths=lengths)
            x = x + h
            x = x + mlp_apply(p["mlp"], self._norm(p["ln2"], x),
                              cfg.mlp_activation)
        else:  # pragma: no cover
            raise ValueError(kind)
        return x, c

    def prefill(self, params, tokens, max_len: int, lengths=None):
        """One-shot serving prefill: full-sequence forward + decode cache.

        tokens: [b, s] int32 with positions 0..s-1.  Returns
        (last-position logits [b, vocab] f32, cache) where the cache has
        exactly the ``init_cache(b, max_len)`` structure, positioned so
        ``decode_step(..., pos=s)`` continues the sequence.  Replaces an
        O(s)-dispatch decode-step prefill with ONE lowered forward.

        ``lengths`` ([b] int32): per-sequence real prompt lengths for
        right-padded (length-bucketed) prefill — one executable serves
        every prompt length in a bucket.  Padding cannot perturb the
        result: attention masks padded keys causally and skips their
        cache rows, recurrent (ssm/rglru) state carries through padded
        steps as an exact identity, MoE dispatch excludes padded
        tokens, and the logits/cache hand-off is taken at ``length-1``
        per sequence (``decode_step(..., pos=length)`` continues).  The
        returned logits and every cache row below ``length`` are
        bit-identical to ``prefill(params, tokens[:, :length], max_len)``
        as long as both sides take the same attention core path (padded
        and real length on the same side of the blocked-attention
        threshold, ``2*attention.QBLOCK``).
        """
        cfg = self.cfg
        x = self._embed(params, tokens)
        x = constrain(x, "B", "S", None)
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if lengths is not None:
            lengths = jnp.asarray(lengths, jnp.int32)

        blocks, stacks = self._split_experts(params["blocks"])

        def group_body(x, inputs):
            gp, g = inputs
            cs = []
            for i, kind in enumerate(cfg.attn_pattern):
                x, c = self._block_prefill(
                    kind, gp[i], x, positions, max_len, lengths=lengths,
                    experts=None if stacks is None else (stacks[i], g))
                x = constrain(x, "B", "S", None)
                cs.append(c)
            return x, tuple(cs)

        if self.unroll:
            per_group = []
            for g in range(cfg.n_groups):
                gp = jax.tree.map(lambda l: l[g], blocks)
                x, cs = group_body(x, (gp, g))
                per_group.append(cs)
            gcaches = jax.tree.map(lambda *ls: jnp.stack(ls), *per_group)
        else:
            x, gcaches = jax.lax.scan(
                group_body, x,
                (blocks, jnp.arange(cfg.n_groups, dtype=jnp.int32)))
        tail_caches = []
        for i, kind in enumerate(cfg.pattern_tail):
            x, c = self._block_prefill(kind, params["tail"][i], x, positions,
                                       max_len, lengths=lengths)
            x = constrain(x, "B", "S", None)
            tail_caches.append(c)
        cache = {"groups": gcaches, "tail": tuple(tail_caches)}
        if lengths is None:
            last = x[:, -1:]
        else:
            idx = jnp.clip(lengths - 1, 0, s - 1)[:, None, None]
            last = jnp.take_along_axis(x, idx, axis=1)
        logits = self._unembed(params, last)[:, 0, :]
        return logits, cache

    def _block_decode(self, kind, p, c, x, pos, backend: str = "gather",
                      layer=None, experts=None):
        """``(x, cache, rows)``: ``rows`` is the expert layer's rows per
        expert, ``None`` where the layer has no experts."""
        cfg = self.cfg
        rows = None
        if kind in ("global", "local"):
            h, c = attn.attn_decode(p["attn"], cfg, self._norm(p["ln1"], x),
                                    c, pos, kind, backend=backend,
                                    layer=layer)
            x = x + h
            y, rows = self._ffn_serve(p, self._norm(p["ln2"], x),
                                      experts=experts)
            x = x + y
        elif kind == "ssm":
            h, c = ssm_mod.ssm_decode(p["ssm"], cfg,
                                      self._norm(p["ln1"], x), c)
            x = x + h
        elif kind == "rglru":
            h, c = rglru_mod.rglru_decode(p["rec"], cfg,
                                          self._norm(p["ln1"], x), c)
            x = x + h
            x = x + mlp_apply(p["mlp"], self._norm(p["ln2"], x),
                              cfg.mlp_activation)
        return x, c, rows

    def decode_step(self, params, cache, token, pos,
                    decode_backend: str = "gather",
                    expert_rows: bool = False):
        """token: [b] int32 (or [b, d] embeds); pos: [] int32, or [b]
        int32 for per-slot positions (continuous batching: each batch
        slot decodes its own sequence offset).

        ``decode_backend``: attention path for paged caches —
        ``"gather"`` (materialize the logical view; bit-identical to a
        contiguous cache) or ``"pallas_paged"`` (the block-table Pallas
        kernel of :mod:`repro.kernels.paged_attention`; no gather).

        Returns (logits [b, vocab] f32, new_cache), and with
        ``expert_rows`` a third output ``[expert layers, n_experts]``
        int32: the rows the step routed to each expert of each layer.

        The KV pools of paged attention layers, stacked over groups,
        ride through the depth loop as its *carry*: layer ``g`` writes
        its new row into group ``g`` of the stacked pool and reads it
        there, so the pools are updated in place and never sliced or
        restacked.  Everything else (params, block tables, contiguous
        caches, recurrent state) is sliced per group as before.
        """
        cfg = self.cfg
        if token.ndim == 2:  # frontend embedding
            x = token[:, None, :].astype(_dtype(cfg))
        else:
            x = self._embed(params, token[:, None])

        def body(carry, inputs):
            x, pools = carry
            gp, gc, g = inputs
            new_cs, new_pools, rows = [], [], []
            for i, kind in enumerate(cfg.attn_pattern):
                pool = pools[i]
                ex = None if stacks is None else (stacks[i], g)
                if pool is None:
                    x, nc, r = self._block_decode(kind, gp[i], gc[i], x, pos,
                                                  backend=decode_backend,
                                                  experts=ex)
                else:
                    c = dataclasses.replace(gc[i], kp=pool[0], vp=pool[1])
                    x, nc, r = self._block_decode(kind, gp[i], c, x, pos,
                                                  backend=decode_backend,
                                                  layer=g, experts=ex)
                    pool = (nc.kp, nc.vp)
                    nc = dataclasses.replace(nc, kp=None, vp=None)
                new_cs.append(nc)
                new_pools.append(pool)
                if r is not None and expert_rows:
                    rows.append(r)
            return (x, tuple(new_pools)), (tuple(new_cs), tuple(rows))

        paged = tuple(isinstance(c, attn.PagedKVCache)
                      for c in cache["groups"])
        pools = tuple((c.kp, c.vp) if p else None
                      for c, p in zip(cache["groups"], paged))
        rest = tuple(dataclasses.replace(c, kp=None, vp=None) if p else c
                     for c, p in zip(cache["groups"], paged))
        carry = (x, pools)
        blocks, stacks = self._split_experts(params["blocks"])
        if self.unroll:
            new_groups = []
            for g in range(cfg.n_groups):
                gp = jax.tree.map(lambda l: l[g], blocks)
                gc = jax.tree.map(lambda l: l[g], rest)
                carry, nc = body(carry, (gp, gc, g))
                new_groups.append(nc)
            new_rest, rows = jax.tree.map(
                lambda *leaves: jnp.stack(leaves), *new_groups)
        else:
            carry, (new_rest, rows) = jax.lax.scan(
                body, carry, (blocks, rest,
                              jnp.arange(cfg.n_groups, dtype=jnp.int32)))
        x, pools = carry
        new_gcache = tuple(
            c if p is None else dataclasses.replace(c, kp=p[0], vp=p[1])
            for c, p in zip(new_rest, pools))
        rows = list(rows)                        # each [n_groups, e]
        new_tail = []
        for i, kind in enumerate(cfg.pattern_tail):
            x, nc, r = self._block_decode(kind, params["tail"][i],
                                          cache["tail"][i], x, pos,
                                          backend=decode_backend)
            new_tail.append(nc)
            if r is not None and expert_rows:
                rows.append(r[None])
        new_cache = {"groups": new_gcache, "tail": tuple(new_tail)}
        logits = self._unembed(params, x)[:, 0, :]
        if not expert_rows:
            return logits, new_cache
        if not rows:
            return logits, new_cache, jnp.zeros((0, cfg.n_experts),
                                                jnp.int32)
        return logits, new_cache, jnp.concatenate(rows)
