"""Serving: sharded prefill/decode step builders + a batched engine.

``build_prefill_step`` / ``build_decode_step`` produce the exact
computations the inference dry-run shapes lower (`prefill_32k` lowers
the full-sequence forward; `decode_32k` / `long_500k` lower ONE decode
step against a materialized KV cache, per the assignment).

Cache sharding: batch on the data axes, heads/state channels on
``model``; for single-sequence long-context (`long_500k`, batch=1) the
policy's ``kv_seq_axis`` shards the cache *length* instead, which GSPMD
turns into flash-decode-style distributed attention.

:class:`ServeEngine` is the production batched loop on top of the
builders: one-shot prefill (a single lowered full-sequence forward per
admitted request, not ``prompt_len`` decode dispatches), continuous
batching over ``max_batch`` slots with per-slot positions (sequences of
mixed prompt lengths admit and retire mid-flight), and a unified
greedy/temperature/top-k sampler applied identically from the *first*
generated token.  Sampling keys are derived per (request, token index),
never from the step loop, so generations are bit-independent of how
requests happen to be batched together.  An optional telemetry sink
(:mod:`repro.serve.telemetry`) accounts the engine's per-step DRAM
traffic into a :class:`repro.core.workload.WorkloadProfile` for the RTC
policy engine.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist.axisenv import axis_env
from repro.dist.sharding import ShardingPolicy, param_specs
from repro.models.attention import RESERVED_PAGES, PagedKVCache
from repro.models.config import ModelConfig
from repro.models.rglru import PagedRGLRUCache
from repro.models.ssm import PagedSSMCache
from repro.models.transformer import TransformerLM
from repro.serve import spans
from repro.serve.paging import (PagedCacheConfig, PageTable, PrefixKeys,
                                prefix_page_keys, slot_floor)
from repro.serve.scheduler import Scheduler
from repro.serve.telemetry import TelemetryHooks

__all__ = ["cache_specs", "build_prefill_step", "build_decode_step",
           "PrefillBuckets", "Request", "ServeEngine"]


def cache_specs(model: TransformerLM, batch: int, cache_len: int,
                policy: ShardingPolicy, kv_seq_axis=None,
                model_axis_size: Optional[int] = None,
                cache_factory=None):
    """PartitionSpec tree matching ``model.init_cache(batch, cache_len)``
    (or ``cache_factory()`` — e.g. a paged cache structure).

    KV placement mirrors ``attention.attn_decode``: shard heads on the
    model axis when there are enough KV heads to fill it, otherwise
    shard the cache length (flash-decode).  ``kv_seq_axis`` overrides
    (long_500k shards the length over the whole mesh).

    Paged-cache leaves (``kp``/``vp`` pools, ``conv_p``/``h_p`` state
    pools, ``block`` tables): pools have no batch dim, so the *page*
    dim takes the data axes instead (``ShardingPolicy.page_spec`` —
    only when provably divisible), heads/state channels keep the model
    axis, and block tables shard their *slot* dim over the data axes
    (``ShardingPolicy.slot_spec``): under the device-local page layout
    each device holds exactly the table rows of the slots pinned to its
    pool extent, which is what lets the ``shard_map`` decode step read
    pools with no collective at all (indivisible slot counts
    replicate, which always lowers).
    """
    cfg = model.cfg
    b = policy.batch_spec if batch > 1 else None
    m = policy.model_axis
    shapes = jax.eval_shape(cache_factory if cache_factory is not None
                            else lambda: model.init_cache(batch, cache_len))
    heads_fit = (model_axis_size is not None and cfg.n_kv_heads > 0
                 and cfg.n_kv_heads % model_axis_size == 0)

    def one(path, leaf):
        name = str(getattr(path[-1], "name", getattr(path[-1], "key", "")))
        nd = len(leaf.shape)
        # "groups" caches carry a leading stacked-group axis; "tail"
        # caches (pattern remainder layers) do not.
        top = str(getattr(path[0], "key", ""))
        lead = (None,) if top == "groups" else ()
        if name in ("k", "v"):            # [(G,) B, L, KV, hd]
            if kv_seq_axis is not None:
                return P(*lead, b, kv_seq_axis, None, None)
            if heads_fit:
                return P(*lead, b, None, m, None)
            return P(*lead, b, m, None, None)
        if name in ("kp", "vp"):          # [(G,) n_pages, P, KV*hd]
            n_pages = leaf.shape[len(lead)]
            if kv_seq_axis is not None:
                # same no-padding rule as page_spec: pjit argument
                # shardings reject indivisible dims, so only shard the
                # page dim when the seq-axis extent provably divides it
                axes = kv_seq_axis if isinstance(kv_seq_axis, tuple) \
                    else (kv_seq_axis,)
                size = 1
                for a in axes:
                    size *= policy.axis_size(a) or 0
                sd = kv_seq_axis if size and n_pages % size == 0 else None
                return P(*lead, sd, None, None)
            pd = policy.page_spec(n_pages)
            if heads_fit:
                # KV*hd is head-major, so splitting it on the model
                # axis splits whole heads, as the contiguous cache does
                return P(*lead, pd, None, m)
            return P(*lead, pd, None, None)
        if name == "block":
            # [(G,) B(, n_lp)] — slot dim rides the data axes with the
            # pool extents; no sharding along kv_seq_axis (the seq-split
            # layout keeps tables replicated for the length gather).
            sd = None if kv_seq_axis is not None \
                else policy.slot_spec(leaf.shape[len(lead)])
            rest = [None] * (nd - len(lead) - 1)
            return P(*lead, sd, *rest)
        if name == "length":
            return P(*([None] * nd))
        if name in ("conv", "conv_p"):     # [(G,) B|n_sp, k-1, width]
            cb = b if name == "conv" \
                else policy.page_spec(leaf.shape[len(lead)])
            return P(*lead, cb, None, m)
        if name in ("h", "h_p"):
            # state pools take the page placement KV pools get: the
            # page dim is the capacity dim, and leaving it replicated
            # makes the per-device state bill grow with the mesh (the
            # partition pass's invariance gate caught exactly this)
            hb = b if name == "h" \
                else policy.page_spec(leaf.shape[len(lead)])
            if nd == len(lead) + 3:        # ssm: [(G,) B|n_sp, di, n]
                return P(*lead, hb, m, None)
            return P(*lead, hb, m)         # rglru: [(G,) B|n_sp, dl]
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(one, shapes)


def build_prefill_step(model: TransformerLM, mesh: Mesh,
                       policy: ShardingPolicy, donate: bool = False,
                       last_only: bool = True,
                       cache_len: Optional[int] = None,
                       batch: Optional[int] = None):
    """Full-sequence forward with sharded params/batch.

    ``last_only`` (production default): unembed only the final position
    — serving prefill needs the first sampled token, not [b, s, vocab]
    logits (4.2 GiB/device of pure output for gemma2-9b @32k).

    ``cache_len`` (serving): also materialize the decode cache — the
    jitted function then lowers ``model.prefill`` and takes a third
    ``lengths`` argument ([b] int32, real prompt lengths of the
    right-padded ``tokens``), returning (logits at ``length-1``
    [b, vocab] f32, cache) with the exact ``init_cache(b, cache_len)``
    structure, ready for ``build_decode_step`` to continue at position
    ``length``.

    ``batch``: the token batch size this step will be fed (the serving
    engine prefills one request at a time).  A batch of 1 replicates
    the batch dimension instead of sharding it — a size-1 dim cannot be
    laid out over a >1-device data axis.  A serving prefill of batch 1
    on a model axis that :func:`_model_split` accepts runs the model in
    shares under ``shard_map``, as the decode step does: logits come out
    split over the vocabulary, the cache over the KV heads.
    """
    pspecs = param_specs(jax.eval_shape(
        lambda: model.init(jax.random.key(0))), policy)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    bspec = policy.batch_spec if (batch is None or batch > 1) else None
    tok_sh = NamedSharding(mesh, P(bspec, policy.seq_axis))

    if cache_len is not None:
        len_sh = NamedSharding(mesh, P(bspec))
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        split = _model_split(model, policy, sizes) if batch == 1 else 1
        if split > 1:
            # the model in shares over the model axis, as the decode
            # step runs it: no expert weight is ever gathered
            m = policy.model_axis

            def body(params, tokens, lengths):
                with axis_env(batch_axes=None, model_axis=None,
                              seq_axis=None, mesh=None, manual=(m, split)):
                    return model.prefill(params, tokens, cache_len,
                                         lengths=lengths)

            cspecs = cache_specs(model, 1, cache_len, policy,
                                 model_axis_size=split)
            prefill_cached = jax.shard_map(
                body, mesh=mesh, in_specs=(pspecs, P(), P()),
                out_specs=(P(None, m), cspecs), check_vma=False)
        else:
            def prefill_cached(params, tokens, lengths):
                with axis_env(policy, mesh=mesh):
                    return model.prefill(params, tokens, cache_len,
                                         lengths=lengths)

        return jax.jit(spans.named("serve_prefill", prefill_cached),
                       in_shardings=(psh, tok_sh, len_sh)), psh, tok_sh

    def prefill(params, tokens):
        with axis_env(policy, mesh=mesh):
            if last_only:
                hidden, _ = model.hidden(params, tokens=tokens)
                return model._unembed(params, hidden[:, -1:])
            logits, _ = model.apply(params, tokens=tokens)
            return logits

    return jax.jit(prefill, in_shardings=(psh, tok_sh)), psh, tok_sh


def _is_paged_node(x) -> bool:
    return isinstance(x, (PagedKVCache, PagedSSMCache, PagedRGLRUCache))


def _model_split(model: TransformerLM, policy: ShardingPolicy,
                 sizes: Dict[str, int]) -> int:
    """Size of the model axis where a serving step runs the model in
    shares over it (``shard_map``, :func:`repro.dist.axisenv.model_shard`),
    else 1.  Shares need attention layers only, with heads, KV heads,
    vocabulary and the MLP's width (or the experts, or every expert's
    width) dividing evenly over the axis, as ``param_specs`` splits
    them, and no FSDP, ZeRO or sequence axis."""
    msize = sizes.get(policy.model_axis, 1)
    cfg = model.cfg
    if cfg.n_experts:
        vs = cfg.moe_virtual_split
        ffn = ((cfg.d_ff // vs) % msize == 0
               or (cfg.n_experts * vs) % msize == 0)
    else:
        ffn = cfg.d_ff % msize == 0
    if (msize <= 1 or not ffn or policy.fsdp or policy.zero1
            or policy.seq_axis is not None
            or any(k not in ("global", "local") for k in cfg.all_kinds)
            or cfg.n_heads % msize or cfg.n_kv_heads % msize
            or cfg.vocab_size % msize):
        return 1
    return msize


def _local_kv_heads(cache, split: int):
    """Paged KV nodes' static ``kv_heads`` divided by ``split``: a
    device's share of the heads inside a model-axis ``shard_map``."""
    return jax.tree.map(
        lambda n: (dataclasses.replace(n, kv_heads=n.kv_heads // split)
                   if isinstance(n, PagedKVCache) else n),
        cache, is_leaf=_is_paged_node)


def _shift_block_ids(cache, shift):
    """Add ``shift * local_pool_extent`` to every paged node's block
    table (``shift`` may be a traced scalar).  Inside a ``shard_map``
    body the pool leaves are already device-local, so each node's own
    page-dim extent *is* the per-shard extent — ``-shard_index``
    rebases global page ids to local pool offsets, ``+shard_index``
    restores them."""
    def one(node):
        if isinstance(node, PagedKVCache):
            ext = node.kp.shape[node.kp.ndim - 3]   # [(G,) n_pages, P, kvh*hd]
            return dataclasses.replace(node, block=node.block + shift * ext)
        ext = node.conv_p.shape[node.conv_p.ndim - 3]  # [(G,) n_sp, k-1, d]
        return dataclasses.replace(node, block=node.block + shift * ext)

    return jax.tree.map(one, cache, is_leaf=_is_paged_node)


def build_decode_step(model: TransformerLM, mesh: Mesh,
                      policy: ShardingPolicy, batch: int, cache_len: int,
                      kv_seq_axis=None, per_slot_pos: bool = False,
                      cache_factory=None, decode_backend: str = "gather",
                      donate_cache: bool = True, shards: int = 1):
    """One-token decode with sharded KV cache. Returns
    (step_fn, param_shardings, cache_shardings).

    ``per_slot_pos``: the position argument is a [batch] vector (each
    slot decodes its own sequence offset — continuous batching) instead
    of one scalar shared by the whole batch.

    ``cache_factory``: overrides the cache structure the step is lowered
    for (the paged engine passes ``PageTable.init_cache`` so the step
    consumes pool + block-table leaves instead of contiguous buffers).

    ``decode_backend``: paged-cache attention path — ``"gather"``
    materializes the logical view, ``"pallas_paged"`` runs the
    block-table Pallas kernel in place.  The cache shardings are the
    same either way (pool page dims keep ``ShardingPolicy.page_spec``):
    the kernel is opaque to GSPMD, which gathers its operands around
    the call while the cache itself stays sharded across steps.

    ``donate_cache``: donate the cache argument into the step (the
    default; in/out cache shardings match, so XLA updates the buffers —
    including paged pool pages — in place instead of copying the full
    cache every token).  The static analyzer's donation lint
    (``repro.analysis``) checks the lowered executable actually carries
    the donation, and its per-step byte accounting *assumes* it: an
    un-donated cache is a copy the traffic cross-check would miss.
    Disable only to lower a step whose caller must keep the input cache
    alive (e.g. checkpoint-restore debugging).

    ``shards``: number of device-local pool extents the paged cache
    geometry was built with (:class:`repro.serve.paging.PageTable`).
    When it matches the mesh's data extent (and every non-data axis has
    size 1, no ``kv_seq_axis``), the step is built as a **shard_map**
    computation: each device rebases its (global-id) block-table rows
    into its local pool extent, runs the full decode — including the
    opaque Pallas paged-attention kernel — strictly device-locally, and
    restores global ids on the way out; the replicated cache ``length``
    is recomputed globally outside the mapped region with the exact
    per-backend formula (``min(max(pos)+1, cache_len)``), so
    generations are bit-identical to the solo/GSPMD step.  No
    collective with a pool operand is lowered at any mesh size — the
    property ``repro.analysis`` gates.  On a model axis that
    :func:`_model_split` accepts, the same ``shard_map`` step also maps
    the model axis: each device runs the model on its share (its query
    and KV heads and their slice of every pool, its expert share, its
    vocabulary slice; :func:`repro.dist.axisenv.model_shard`), each
    layer sums its attention output and its MLP or expert output over
    the axis, and logits come out split over the vocabulary.  On any
    mismatch the builder falls back to the plain GSPMD step, which is
    always correct (the global-id layout decodes unmapped as-is) but
    gathers the pools around the kernel.

    A model with experts returns a third output, the rows each expert
    of each layer was routed (``decode_step(..., expert_rows=True)``).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pspecs = param_specs(jax.eval_shape(
        lambda: model.init(jax.random.key(0))), policy)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))
    cspecs = cache_specs(model, batch, cache_len, policy, kv_seq_axis,
                         model_axis_size=sizes.get(policy.model_axis),
                         cache_factory=cache_factory)
    csh = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                       is_leaf=lambda x: isinstance(x, P))
    tok_sh = NamedSharding(
        mesh, P(policy.batch_spec if batch > 1 else None))
    if per_slot_pos:
        pos_sh = NamedSharding(
            mesh, P(policy.batch_spec if batch > 1 else None))
    else:
        pos_sh = NamedSharding(mesh, P())

    with_rows = bool(model.cfg.n_experts)

    def decode(params, cache, token, pos):
        seq_override = kv_seq_axis if kv_seq_axis is not None else policy.seq_axis
        with axis_env(batch_axes=policy.data_axes if batch > 1 else None,
                      model_axis=policy.model_axis,
                      seq_axis=seq_override, mesh=mesh):
            return model.decode_step(params, cache, token, pos,
                                     decode_backend=decode_backend,
                                     expert_rows=with_rows)

    data_size = 1
    for a in policy.data_axes:
        data_size *= sizes.get(a, 1)
    split = _model_split(model, policy, sizes)
    use_shard_map = (
        cache_factory is not None and kv_seq_axis is None
        # data shards each own a pool extent, or there is one extent
        and (data_size == shards if data_size > 1 else shards == 1)
        and (shards > 1 or split > 1)
        # FSDP/ZeRO scatter params over the data axes; under a manual
        # map nothing re-gathers them, so the body would compute on
        # weight shards — GSPMD fallback stays correct there.
        and not policy.fsdp and not policy.zero1
        and all(s == 1 for a, s in sizes.items()
                if a not in policy.data_axes
                and (split == 1 or a != policy.model_axis)))
    if use_shard_map:
        bspec = policy.batch_spec
        m = policy.model_axis if split > 1 else None
        manual = (m, split) if split > 1 else None

        def body(params, cache, token, pos):
            # flat data-shard index, from static axis sizes (partition-id
            # arithmetic only: the body's only collectives are the model
            # axis's sums of each layer's partial outputs)
            g = jnp.int32(0)
            for a in policy.data_axes:
                g = g * sizes.get(a, 1) + jax.lax.axis_index(a)
            local = _local_kv_heads(_shift_block_ids(cache, -g), split)
            # mesh=None env: `constrain` is the identity — the body is
            # already device-local, GSPMD has nothing to place.
            with axis_env(batch_axes=None, model_axis=None, seq_axis=None,
                          mesh=None, manual=manual):
                out = model.decode_step(params, local, token, pos,
                                        decode_backend=decode_backend,
                                        expert_rows=with_rows)
            new_cache = _shift_block_ids(out[1], g)
            # `length` is replicated (out_spec P()): pass the incoming
            # replicated value through; the wrapper below recomputes it
            # from the *global* position vector, exactly as the unmapped
            # step does — per-device lengths would diverge.  `kv_heads`
            # goes back to the whole model's count.
            new_cache = jax.tree.map(
                lambda new, old: (dataclasses.replace(
                    new, length=old.length, kv_heads=old.kv_heads)
                    if isinstance(new, PagedKVCache) else new),
                new_cache, cache, is_leaf=_is_paged_node)
            return (out[0], new_cache) + out[2:]

        # the routed rows are computed alike on every device
        smap = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, cspecs, P(bspec),
                      P(bspec) if per_slot_pos else P()),
            out_specs=(P(bspec, m), cspecs) + ((P(),) if with_rows else ()),
            check_vma=False)

        def decode_sm(params, cache, token, pos):
            out = smap(params, cache, token, pos)
            new_cache = jax.tree.map(
                lambda new: (dataclasses.replace(
                    new, length=jnp.broadcast_to(
                        jnp.minimum(jnp.max(pos) + 1,
                                    new.cache_len).astype(jnp.int32),
                        new.length.shape))
                    if isinstance(new, PagedKVCache) else new),
                out[1], is_leaf=_is_paged_node)
            return (out[0], new_cache) + out[2:]

        fn = decode_sm
    else:
        fn = decode

    step = jax.jit(
        spans.named("serve_decode", fn),
        in_shardings=(psh, csh, tok_sh, pos_sh),
        out_shardings=(NamedSharding(mesh, P(
            policy.batch_spec if batch > 1 else None, None)), csh)
        + ((NamedSharding(mesh, P()),) if with_rows else ()),
        donate_argnums=(1,) if donate_cache else (),
    )
    return step, psh, csh


# ---------------------------------------------------------------------------
# Prefill bucketing policy
# ---------------------------------------------------------------------------
class PrefillBuckets:
    """Length-bucket ladder for prefill, with pad-waste accounting.

    Prompts are right-padded up to the smallest ladder entry that fits
    (best-fit), so the number of distinct prefill shapes — and therefore
    the number of lowered prefill executables — is bounded by
    ``len(ladder)`` regardless of the traffic's length distribution.
    Entries above ``max_len`` are dropped and ``max_len`` itself is
    always the top rung (every admissible prompt fits somewhere).

    Counters accumulate across serve calls: ``hits`` per bucket,
    ``real_tokens`` vs ``padded_tokens``, and ``pad_waste`` (the
    fraction of padded prefill positions that carried no prompt token)
    — the knob to watch when tuning a ladder against a traffic mix.
    """

    def __init__(self, ladder: Sequence[int], max_len: Optional[int] = None):
        rungs = sorted({int(x) for x in ladder})
        if not rungs or rungs[0] < 1:
            raise ValueError(f"bucket ladder must be positive ints: {ladder}")
        if max_len is not None:
            rungs = [x for x in rungs if x < max_len] + [int(max_len)]
        self.ladder: Tuple[int, ...] = tuple(rungs)
        self.hits = {x: 0 for x in self.ladder}
        self.real_tokens = 0
        self.padded_tokens = 0

    @classmethod
    def powers_of_two(cls, max_len: int, min_bucket: int = 8
                      ) -> "PrefillBuckets":
        """Default ladder: min_bucket, 2*min_bucket, ... capped at max_len."""
        if min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, got {min_bucket}")
        rungs, b = [], int(min_bucket)
        while b < max_len:
            rungs.append(b)
            b *= 2
        return cls(rungs + [int(max_len)], max_len=max_len)

    def bucket_for(self, plen: int) -> int:
        """Smallest rung that fits ``plen`` (best-fit)."""
        for b in self.ladder:
            if plen <= b:
                return b
        raise ValueError(
            f"prompt length {plen} exceeds top bucket {self.ladder[-1]}")

    def record(self, plen: int, bucket: int) -> None:
        self.hits[bucket] += 1
        self.real_tokens += int(plen)
        self.padded_tokens += int(bucket)

    @property
    def pad_waste(self) -> float:
        """Fraction of prefilled positions that were padding."""
        if not self.padded_tokens:
            return 0.0
        return 1.0 - self.real_tokens / self.padded_tokens

    def stats(self) -> dict:
        return {"ladder": self.ladder,
                "hits": dict(self.hits),
                "real_tokens": self.real_tokens,
                "padded_tokens": self.padded_tokens,
                "pad_waste": self.pad_waste}

    def summary(self) -> str:
        hits = " ".join(f"{b}:{n}" for b, n in self.hits.items() if n)
        return (f"buckets {list(self.ladder)} hits [{hits}] "
                f"pad waste {self.pad_waste:.1%} "
                f"({self.padded_tokens - self.real_tokens} of "
                f"{self.padded_tokens} prefill positions)")


# ---------------------------------------------------------------------------
# Batched serving engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class Request:
    """One admitted generation request (engine-internal ids).

    ``eq=False``: the ndarray prompt makes generated equality/hash
    raise; identity comparison is the useful semantic for requests.
    Sampling params live on the request — mixed greedy/temperature
    traffic batches together, each request keeping its own schedule-
    independent generation.
    """
    req_id: int
    prompt: np.ndarray          # [plen] int32, plen >= 1
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    # arrival index: the scheduler's FCFS/victim ordering (req_id is the
    # caller-visible sampling identity and may arrive in any order)
    order: int = 0
    # content-addressed page keys when the engine serves with prefix
    # sharing (None otherwise)
    keys: Optional[PrefixKeys] = None


class ServeEngine:
    """Continuous-batching serving loop over ``max_batch`` cache slots.

    Requests of mixed prompt lengths are admitted into free slots
    mid-flight (one-shot prefill + cache insertion), decoded together
    with per-slot positions, and retired on EOS / request budget /
    ``max_len`` — the freed slot is immediately refilled from the
    pending queue.  Slot admission order never changes a request's
    tokens: sampling keys are a pure function of (seed, request id,
    token index).

    Compile note: prompts are right-padded up to a
    :class:`PrefillBuckets` ladder and prefilled through the masked
    ``model.prefill(..., lengths=...)`` path, so the number of lowered
    prefill executables is bounded by the ladder size regardless of the
    traffic's length distribution — and padding provably cannot perturb
    a generation (attention masks padded keys, recurrent ssm/rglru
    state carries through padded steps as an exact identity, MoE
    dispatch excludes padded tokens, and the logits/cache hand-off is
    taken at ``length-1``).

    Sampling params (``temperature`` / ``top_k``) are per *request*:
    ``serve`` accepts either one value for the whole call or a
    per-prompt sequence, and a mixed greedy+stochastic batch reproduces
    each request's solo generation bit-for-bit.

    ``paged=PagedCacheConfig(...)`` switches the decode cache to
    block-table paging (:mod:`repro.serve.paging` — design note in the
    package docstring): slots grow page lists allocate-on-write up to
    ``max_ctx`` (which may exceed ``max_len``, the prefill cap), and
    when the resident-page budget runs dry the newest live request is
    preempted, its pages offloaded to host, and resumed — bit-
    identically — once pages free up.  Paged and contiguous serving
    produce identical tokens for any in-budget workload.

    ``decode_backend`` selects how paged attention resolves the block
    tables: ``"gather"`` (default) materializes the contiguous logical
    view every step — bit-identical to contiguous serving but a full
    cache-length copy per layer per step; ``"pallas_paged"`` runs the
    :mod:`repro.kernels.paged_attention` kernel, which reads K/V pages
    through the block-table indirection in place (compiled on a TPU,
    interpreted elsewhere).  Generations are identical across backends
    on every arch (logits agree to accumulation-order tolerance; pinned in
    ``tests/test_paged_attention_kernel.py``), and telemetry accounts
    only true per-page reads on the kernel path — no materialized-view
    traffic.

    ``PagedCacheConfig(sharing=PrefixSharingConfig(...))`` turns on
    prefix sharing (PR 10 — full design note in the
    :mod:`repro.serve` package docstring): prompts are chain-hashed
    into per-page content keys at submission, admission attaches
    registry hits instead of re-allocating (copy-on-write protects the
    shared pages — :class:`~repro.serve.paging.PageTable`), an
    exact-duplicate prompt skips its prefill outright by replaying the
    memoized first-token logits and restoring recurrent state from a
    host snapshot, and the admission scheduler groups same-prefix
    pending requests so their residency windows overlap.  Both paths
    are bit-identical to unshared serving on every arch (the all-arch
    suite in ``tests/test_prefix_sharing.py`` pins it).

    The engine owns the compiled programs and the cache layout; each
    :meth:`serve` call's state (slots, queues, memo) lives in a
    :class:`~repro.serve.scheduler.Scheduler`.
    """

    def __init__(self, model: TransformerLM, params: dict,
                 max_len: int = 256, max_batch: int = 8,
                 eos_id: Optional[int] = None, bos_id: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 policy: Optional[ShardingPolicy] = None,
                 buckets=None, paged=None, decode_backend: str = "gather"):
        self.model = model
        self.params = params
        self.max_len = int(max_len)
        self.max_batch = int(max_batch)
        self.eos_id = eos_id
        self.bos_id = bos_id
        if decode_backend not in ("gather", "pallas_paged"):
            raise ValueError(
                f"decode_backend must be 'gather' or 'pallas_paged', "
                f"got {decode_backend!r}")
        self.decode_backend = decode_backend
        if paged is True:
            paged = PagedCacheConfig()
        self.paged: Optional[PagedCacheConfig] = paged or None
        if decode_backend == "pallas_paged" and self.paged is None:
            raise ValueError(
                "decode_backend='pallas_paged' consumes block tables: "
                "construct the engine with paged=PagedCacheConfig(...)")
        if self.paged is not None:
            self.max_ctx = int(self.paged.max_ctx or self.max_len)
            if self.max_ctx < self.max_len:
                raise ValueError(
                    f"PagedCacheConfig.max_ctx={self.max_ctx} < engine "
                    f"max_len {self.max_len}: the prefill cap cannot "
                    f"exceed the logical context capacity")
            # fail on a bad paged config NOW, before the (expensive)
            # prefill/decode builders lower anything — the same checks
            # PageTable applies, surfaced with the config field named.
            self.paged.validate(model.cfg, self.max_ctx)
        else:
            self.max_ctx = self.max_len
        if buckets is None:
            buckets = PrefillBuckets.powers_of_two(self.max_len)
        elif not isinstance(buckets, PrefillBuckets):
            buckets = PrefillBuckets(buckets, max_len=self.max_len)
        if buckets.ladder[-1] != self.max_len:
            # a short ladder leaves admissible prompts (plen <= max_len)
            # with no bucket and fails mid-serve after other requests
            # already ran; a tall one lowers shapes past the cache that
            # only ever carry masked padding.  The clipped constructor
            # always tops out at exactly max_len.
            raise ValueError(
                f"bucket ladder top {buckets.ladder[-1]} != engine "
                f"max_len {self.max_len}: pass the raw ladder (or build "
                f"with PrefillBuckets(ladder, max_len=...)) so it is "
                f"clipped and capped to the engine")
        self.buckets = buckets
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                        ("data", "model"))
        if policy is None:
            policy = ShardingPolicy.for_mesh(mesh)
        self.mesh, self.policy = mesh, policy
        # prefill materializes a max_ctx-long contiguous cache (== max_len
        # unless paged): positions are then identical between the
        # prefilled cache and the (possibly longer) decode layout, so
        # slot insertion is a pure copy/scatter for every layer kind.
        self._prefill = build_prefill_step(
            model, mesh, policy, cache_len=self.max_ctx, batch=1)[0]
        #: (rows a block, blocks a slot) of the paged kernel's walk
        self._kernel_walk = None
        sh = self.paged.sharing if self.paged is not None else None
        self._sharing = sh if (sh is not None and sh.enabled) else None
        if self.paged is not None:
            shards = self._resolve_shards()
            self._table = PageTable(
                model, self.max_batch, self.max_ctx, self.paged.page_size,
                self.paged.resident_pages,
                state_pages=self.paged.state_pages, shards=shards)
            self._decode, _, self._cache_sh = build_decode_step(
                model, mesh, policy, batch=self.max_batch,
                cache_len=self.max_ctx, per_slot_pos=True,
                cache_factory=self._table.init_cache,
                decode_backend=self.decode_backend, shards=shards)
            self._table.bind_shardings(self._cache_sh)
            self._insert = None
            if decode_backend == "pallas_paged" and any(
                    k in ("global", "local") for k in model.cfg.all_kinds):
                self._kernel_walk = self._block_walk(mesh, policy)
        else:
            self._table = None
            self._decode, _, self._cache_sh = build_decode_step(
                model, mesh, policy, batch=self.max_batch,
                cache_len=self.max_len, per_slot_pos=True)
            # pin the insert output to the decode step's cache shardings,
            # so the slot-update round trip stays layout-stable on real
            # meshes (decode donates and re-emits the same placement).
            # The batch cache is donated: an admit is a single-slot
            # dynamic_update_slice, and without donation every admission
            # copied the full max_batch cache (the donation lint in
            # repro.analysis flagged exactly this executable).
            self._insert = jax.jit(
                spans.named("serve_insert", self._insert_cache),
                out_shardings=self._cache_sh, donate_argnums=(0,))
        self._keys = jax.jit(spans.named("serve_sample_keys", jax.vmap(
            lambda base, r, i: jax.random.fold_in(jax.random.fold_in(base, r), i),
            in_axes=(None, 0, 0))))
        self._sample = jax.jit(spans.named("serve_sample", self._sample_fn),
                               static_argnums=(4,))
        #: the last decode step's rows per layer and expert (device array;
        #: models with experts)
        self.expert_rows = None

    def _block_walk(self, mesh: Mesh, policy: ShardingPolicy):
        """(rows of one kernel block, blocks a slot) of the paged kernel's
        walk over a whole-context block table, at the pool width each
        device's kernel sees."""
        from repro.kernels.paged_attention.kernel import pages_per_block
        cfg, page = self.model.cfg, self.paged.page_size
        split = _model_split(self.model, policy,
                             dict(zip(mesh.axis_names, mesh.devices.shape)))
        n_lp = -(-self.max_ctx // page)
        ppb = pages_per_block(
            page, cfg.n_kv_heads // split * cfg.resolved_head_dim,
            jnp.dtype(cfg.dtype).itemsize, n_lp)
        return page * ppb, -(-n_lp // ppb)

    def _resolve_shards(self) -> int:
        """Device-local pool extents for the paged cache geometry.

        An explicit ``PagedCacheConfig.shards`` wins (the partitioning
        auditor builds mesh-shaped geometry on a compile-only solo
        mesh); otherwise auto-resolve to the mesh's data extent when
        slots and pool budgets split evenly *and* every per-shard
        extent still holds one fully decoded slot — else stay at 1
        (single-pool geometry + GSPMD decode, correct everywhere)."""
        cfgp = self.paged
        if cfgp.shards > 1:
            return cfgp.shards
        shards = self.policy.decode_shards(
            self.max_batch, cfgp.resident_pages, cfgp.state_pages)
        if shards > 1 and cfgp.resident_pages is not None:
            floor = slot_floor(self.model.cfg, self.max_ctx, cfgp.page_size)
            if cfgp.resident_pages // shards < floor:
                return 1
        if shards > 1 and cfgp.state_pages is not None:
            if cfgp.state_pages < self.max_batch + shards * RESERVED_PAGES:
                return 1
        return shards

    @property
    def page_table(self) -> Optional[PageTable]:
        """The engine's :class:`~repro.serve.paging.PageTable` in paged
        mode (``None`` for the contiguous cache) — the public handle to
        the resolved page budget and per-stream allocator state."""
        return self._table

    # ------------------------------------------------------- introspection
    def lowered_artifacts(self, mesh=None,
                          policy: Optional[ShardingPolicy] = None
                          ) -> List[dict]:
        """The engine's lowered executables, packaged for static analysis.

        Returns one entry per executable the serve loop dispatches —
        the decode step, the top prefill bucket, and (contiguous
        engines) the slot-insert — each a dict of the jitted function,
        abstract arguments to trace/lower it with, per-argument roles
        (``params`` / ``cache`` / ``other``), the argnums the engine
        *semantically requires* to be donated, and the argument
        shardings.  Everything is abstract (``jax.eval_shape`` /
        ``ShapeDtypeStruct``): ``repro.analysis`` traces and lowers
        these without executing anything, so an engine constructed with
        abstract params works.  The serve loop itself never calls this.

        ``mesh`` (optionally with ``policy``) rebuilds the step
        functions bound to a *target* mesh — concrete or a
        ``jax.sharding.AbstractMesh`` description — with the engine's
        geometry (batch, context, page budget) unchanged and the
        engine's own executables untouched.  An abstract mesh is bound
        to compile-only host devices via
        :func:`repro.dist.sharding.as_concrete_mesh` (this jax cannot
        lower on an abstract mesh directly); the partitioning pass in
        ``repro.analysis.partition`` uses this to dry-run GSPMD at
        8/64/512 devices on hardware that can execute on at most two.
        """
        if mesh is None and policy is None:
            decode_fn, prefill_fn = self._decode, self._prefill
            insert_fn, cache_sh = self._insert, self._cache_sh
        else:
            from repro.dist.sharding import as_concrete_mesh
            target = mesh if mesh is not None else self.mesh
            lower_mesh = as_concrete_mesh(target)
            pol = policy if policy is not None \
                else ShardingPolicy.for_mesh(target)
            prefill_fn = build_prefill_step(
                self.model, lower_mesh, pol, cache_len=self.max_ctx,
                batch=1)[0]
            if self._table is not None:
                decode_fn, _, cache_sh = build_decode_step(
                    self.model, lower_mesh, pol, batch=self.max_batch,
                    cache_len=self.max_ctx, per_slot_pos=True,
                    cache_factory=self._table.init_cache,
                    decode_backend=self.decode_backend,
                    shards=self._table.shards)
                insert_fn = None
            else:
                decode_fn, _, cache_sh = build_decode_step(
                    self.model, lower_mesh, pol, batch=self.max_batch,
                    cache_len=self.max_len, per_slot_pos=True)
                insert_fn = jax.jit(
                    spans.named("serve_insert", self._insert_cache),
                    out_shardings=cache_sh, donate_argnums=(0,))
        aparams = jax.eval_shape(
            lambda: self.model.init(jax.random.key(0)))
        B = self.max_batch
        if self._table is not None:
            cache = jax.eval_shape(self._table.init_cache)
        else:
            cache = jax.eval_shape(
                lambda: self.model.init_cache(B, self.max_len))
        tok = jax.ShapeDtypeStruct((B,), jnp.int32)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32)
        arts = [dict(
            name="decode", fn=decode_fn, args=(aparams, cache, tok, pos),
            roles={0: "params", 1: "cache"},
            expect_donate_argnums=(1,),
            shardings=(None, cache_sh, None, None))]
        top = self.buckets.ladder[-1]
        arts.append(dict(
            name="prefill", fn=prefill_fn,
            args=(aparams, jax.ShapeDtypeStruct((1, top), jnp.int32),
                  jax.ShapeDtypeStruct((1,), jnp.int32)),
            roles={0: "params"}, expect_donate_argnums=(),
            shardings=None))
        if insert_fn is not None:
            one = jax.eval_shape(
                lambda: self.model.init_cache(1, self.max_ctx))
            arts.append(dict(
                name="insert", fn=insert_fn,
                args=(cache, one, jax.ShapeDtypeStruct((), jnp.int32)),
                roles={0: "cache"},
                expect_donate_argnums=(0,),
                shardings=(cache_sh, None, None)))
        return arts

    @property
    def prefill_executables(self) -> int:
        """Distinct lowered prefill executables (one per bucket shape
        traced) — the quantity the ladder bounds.  Read from the jit
        cache when jax exposes it (private introspection, so a getattr
        fallback counts buckets hit instead — equal whenever every
        recorded bucket was lowered by this engine instance)."""
        cache_size = getattr(self._prefill, "_cache_size", None)
        if cache_size is not None:
            return int(cache_size())
        return sum(1 for n in self.buckets.hits.values() if n)

    # ------------------------------------------------------------- sampling
    @staticmethod
    def _sample_fn(logits, keys, temperature, top_k, use_top_k):
        """Unified greedy / temperature / top-k sampler, vectorized over
        per-request params.

        logits [n, vocab]; temperature [n] f32; top_k [n] int32 (the
        vocab size means "no top-k filter": the kth threshold is then
        the row minimum, which keeps every logit bit-unchanged — so a
        no-filter row draws identically whether or not its batch
        company triggered the filter).  ``use_top_k`` is static: calls
        where NO live request filters skip the O(vocab log vocab) row
        sort entirely (the default greedy/temperature hot path).  Every
        emitted token — including the one sampled from prefill logits —
        goes through this one row-wise function, so params apply from
        the first token and a row's draw is independent of its batch
        company.
        """
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits.astype(jnp.float32) \
            / jnp.maximum(temperature, 1e-6)[:, None]
        if use_top_k:
            vocab = logits.shape[-1]
            srt = jnp.sort(scaled, axis=-1)
            kth = jnp.take_along_axis(
                srt, (vocab - jnp.clip(top_k, 1, vocab))[:, None], axis=-1)
            scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
        drawn = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temperature > 0, drawn.astype(jnp.int32), greedy)

    @staticmethod
    def _per_request(value, n: int, name: str) -> list:
        """Broadcast a scalar-or-sequence sampling param to one per request.

        ``np.ndim == 0`` (not ``np.isscalar``) so 0-d numpy/jax scalars
        — e.g. a temperature coming out of a jax computation — keep
        working as call-wide values.
        """
        if value is None or np.ndim(value) == 0:
            return [value] * n
        vals = list(value)
        if len(vals) != n:
            raise ValueError(
                f"{name}: got {len(vals)} values for {n} prompts")
        return vals

    # ---------------------------------------------------------- cache insert
    @staticmethod
    def _insert_cache(cache, one, slot):
        """Write a prefilled batch-1 cache into batch slot ``slot``."""
        def ins(path, big, small):
            name = str(getattr(path[-1], "name",
                               getattr(path[-1], "key", "")))
            if name == "length":
                # single high-water mark shared by the batch; the decode
                # path recomputes per-slot validity from positions.
                return jnp.maximum(big, small)
            ax = 1 if str(getattr(path[0], "key", "")) == "groups" else 0
            start = [0] * big.ndim
            start[ax] = slot
            return jax.lax.dynamic_update_slice(big, small, tuple(start))

        return jax.tree_util.tree_map_with_path(ins, cache, one)

    # -------------------------------------------------------------- requests
    def _admit_prompt(self, prompt, idx: int) -> np.ndarray:
        p = np.asarray(prompt, np.int32).reshape(-1)
        if p.size == 0:
            if self.bos_id is None:
                raise ValueError(
                    f"empty prompt at index {idx}: generation must start "
                    "from at least one token; construct the engine with "
                    "bos_id= to serve BOS-only requests")
            p = np.asarray([self.bos_id], np.int32)
        top = self.buckets.ladder[-1]
        if p.size > top:
            # validate here, with the request named, instead of failing
            # opaquely inside PrefillBuckets.bucket_for mid-serve (after
            # other requests already ran).
            raise ValueError(
                f"prompt {idx} has length {p.size}, which exceeds the "
                f"largest prefill bucket {top} (engine max_len "
                f"{self.max_len}); split the prompt or raise max_len")
        return p

    # ------------------------------------------------------------ step API
    def new_cache(self):
        """An empty cache for ``max_batch`` slots (paged: every page of
        the table is freed first)."""
        if self._table is not None:
            self._table.reset()
            return self._table.init_cache()
        return self.model.init_cache(self.max_batch, self.max_len)

    def prefill_into(self, cache, slot: int, prompt: np.ndarray,
                     keys: Optional[PrefixKeys] = None):
        """Prefill one prompt, right-padded to its bucket, and write its
        cache into batch slot ``slot``: the paged table admits it
        (deduplicating against ``keys`` under prefix sharing), the
        contiguous cache inserts it.  Returns ``(logits [1, vocab],
        cache, one)`` with ``one`` the batch-1 prefilled cache."""
        plen = prompt.shape[0]
        bucket = self.buckets.bucket_for(plen)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        with spans.span("serve.prefill"):
            logits, one = self._prefill(self.params, jnp.asarray(padded),
                                        jnp.asarray([plen], jnp.int32))
        with spans.span("page_table.insert"):
            if self._table is not None:
                cache = self._table.admit(cache, one, slot, plen, keys)
            else:
                cache = self._insert(cache, one,
                                     jnp.asarray(slot, jnp.int32))
        self.buckets.record(plen, bucket)
        return logits, cache, one

    def decode_step(self, cache, tokens: np.ndarray, positions: np.ndarray):
        """One decode step over every slot: ``tokens`` and ``positions``
        are ``[max_batch]`` int32.  A paged cache must already hold the
        page each slot writes (``page_table.prepare_step``).  Returns
        ``(logits [max_batch, vocab] f32, cache)``; a model with experts
        keeps the step's rows per expert and layer, on the device, in
        ``expert_rows``."""
        out = self._decode(self.params, cache, jnp.asarray(tokens),
                           jnp.asarray(positions))
        if len(out) == 3:
            self.expert_rows = out[2]
        return out[0], out[1]

    # ----------------------------------------------------------------- serve
    def serve(self, prompts: Sequence[np.ndarray], max_new_tokens: int,
              temperature: float = 0.0, top_k: Optional[int] = None,
              seed: int = 0, eos_id: Optional[int] = None,
              telemetry=None,
              request_ids: Optional[Sequence[int]] = None
              ) -> List[np.ndarray]:
        """Serve a batch of requests with continuous batching.

        prompts: sequence of 1-D int32 token arrays (mixed lengths fine
        — each is padded up to the engine's :class:`PrefillBuckets`
        ladder; empty prompts require ``bos_id``).  Returns the
        generated tokens of each request, in input order (each up to
        ``max_new_tokens``, shorter on EOS or cache exhaustion).

        ``temperature`` / ``top_k`` are per *request*: pass one value
        for the whole call, or a sequence with one entry per prompt
        (greedy and stochastic requests batch together; each request's
        generation matches its solo serve bit-for-bit).  ``eos_id``
        overrides the engine default for this call.  ``telemetry`` is an
        optional sink with any of the hooks
        :class:`repro.serve.telemetry.TelemetryHooks` lists (all of them:
        :class:`repro.serve.telemetry.ServeTelemetry`); prefill traffic
        is accounted from true prompt lengths, never padded ones.  A
        sink carrying a ``trace``
        (:class:`repro.core.trace.PageAccessTrace`) additionally gets
        the per-step page-access stream of a *paged* engine: each
        decode step records every pool page it read/wrote (KV sweeps +
        appends, state pages), with admissions, restores, and page-out
        reads folded into the step they precede.

        ``request_ids`` — caller-supplied stable id per prompt (default
        ``0..n-1`` in input order).  The id seeds the request's
        sampling keys and labels its telemetry/trace attribution, so it
        MUST be unique within the call: duplicates are rejected up
        front with the colliding indices named (two requests sharing an
        id would silently alias each other's sampling stream).  Outputs
        stay in *input* order regardless of the ids.

        Each call records spans and counters (:mod:`repro.serve.spans`)
        while a profiler trace is being collected.
        """
        with spans.span("serve.call") as call:
            requests = self._requests(prompts, max_new_tokens, temperature,
                                      top_k, request_ids)
            hooks = TelemetryHooks(telemetry)
            # which decode path moves the KV bytes: the gather path's
            # materialized logical view is traffic the kernel never makes
            hooks.configure_decode(backend=self.decode_backend,
                                   paged=self._table is not None)
            if max_new_tokens == 0:
                return [np.zeros((0,), np.int32) for _ in requests]
            eos = self.eos_id if eos_id is None else eos_id
            sched = Scheduler(self, requests, hooks, seed, eos,
                              t_entry=call.start_ns)
            while sched.busy():
                sched.step()
            return sched.finish()

    def _requests(self, prompts, max_new_tokens, temperature, top_k,
                  request_ids) -> List[Request]:
        """:meth:`serve`'s arguments checked and made into requests,
        ``order`` their input index."""
        if max_new_tokens < 0:
            raise ValueError("max_new_tokens must be >= 0")
        n_req = len(prompts)
        if request_ids is None:
            rids = list(range(n_req))
        else:
            rids = [int(r) for r in request_ids]
            if len(rids) != n_req:
                raise ValueError(
                    f"request_ids: got {len(rids)} ids for {n_req} prompts")
        seen: Dict[int, int] = {}
        for i, rid in enumerate(rids):
            if rid < 0:
                raise ValueError(
                    f"request id {rid} at index {i} is negative; ids seed "
                    f"sampling keys and must be non-negative ints")
            if rid in seen:
                raise ValueError(
                    f"duplicate request id {rid} at indices {seen[rid]} "
                    f"and {i}: ids address sampling keys and telemetry/"
                    f"trace attribution, so two requests sharing one "
                    f"would silently alias")
            seen[rid] = i
        vocab = self.model.cfg.vocab_size
        temps = self._per_request(temperature, n_req, "temperature")
        top_ks = self._per_request(top_k, n_req, "top_k")
        for i, (t, tk) in enumerate(zip(temps, top_ks)):
            if tk is not None and tk < 1:
                raise ValueError(
                    f"top_k must be >= 1, got {tk} (request {i})")
            # a negative temperature flips the softmax ordering and NaN
            # poisons every draw — reject with the request named, same
            # as the top_k check, instead of sampling garbage silently.
            if t is not None and (not np.isfinite(float(t)) or float(t) < 0):
                raise ValueError(
                    f"temperature must be finite and >= 0, got {t} "
                    f"(request {i})")
        requests = []
        for i, (p, t, tk) in enumerate(zip(prompts, temps, top_ks)):
            prompt = self._admit_prompt(p, i)
            keys = (prefix_page_keys(prompt, self.paged.page_size)
                    if self._sharing is not None else None)
            requests.append(Request(
                rids[i], prompt, max_new_tokens, temperature=float(t),
                top_k=vocab if tk is None else int(tk), order=i, keys=keys))
        return requests

    # -------------------------------------------------------------- generate
    def generate(self, prompts: np.ndarray, n_new: int,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 seed: int = 0, eos_id: Optional[int] = None) -> np.ndarray:
        """prompts: [b, prompt_len] int32 -> [b, n_new] int32.

        Batch-API wrapper over :meth:`serve`; sequences that retire
        early are right-padded with the EOS id, or with -1 (never a
        valid vocab id) when no EOS is configured — cache-exhaustion
        truncation must stay distinguishable from generated tokens.
        """
        prompts = np.asarray(prompts, np.int32)
        outs = self.serve(list(prompts), n_new, temperature=temperature,
                          top_k=top_k, seed=seed, eos_id=eos_id)
        eos = self.eos_id if eos_id is None else eos_id
        pad = eos if eos is not None else -1
        full = np.full((len(outs), n_new), pad, np.int32)
        for i, o in enumerate(outs):
            full[i, :o.shape[0]] = o
        return full
