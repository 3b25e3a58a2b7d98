"""Distribution layer: sharding policy + axis environment.

The package has two halves, split by *when* sharding decisions are
made:

* :mod:`repro.dist.sharding` — **static placement**.
  :class:`~repro.dist.sharding.ShardingPolicy` names the mesh axes
  (data axes, model axis, optional sequence axis) and the FSDP/ZeRO-1
  regime; :func:`~repro.dist.sharding.param_specs` maps a parameter
  pytree (shapes only — works under ``jax.eval_shape``) to a
  ``PartitionSpec`` pytree.

* :mod:`repro.dist.axisenv` — **dynamic constraints**.
  ``with axis_env(policy, mesh=mesh):`` binds logical dimension tags
  to mesh axes inside a traced computation, and
  ``constrain(x, "B", None, "M")`` re-shards intermediates without
  the model code ever naming a concrete mesh axis.

Axis-env semantics
==================

Tags are single letters: ``"B"`` (batch -> the policy's data axes),
``"S"`` (sequence -> the policy's ``seq_axis``, usually ``None``),
``"M"`` (model/tensor-parallel axis), and ``None`` (unsharded).  Tag
resolution *dedups left to right*: a mesh axis consumed by an earlier
dimension is dropped from later tags (a tag whose axes are all taken
resolves to ``None`` rather than producing an invalid spec), so model
code can tag dimensions optimistically — e.g. sequence-sharding over
the whole mesh leaves ``"M"`` empty.  Outside any env (or without a
mesh) ``constrain`` is the identity, which keeps the pure-CPU unit
tests and ``eval_shape`` paths free of device state.

Sharding rule table (``param_specs``)
=====================================

Stacked block parameters carry a leading group (scan) dim that is
never sharded.  ``m`` is the policy's model axis.

==========  =============  ========================================
module      tensor         rule
==========  =============  ========================================
embed       tok [V, d]     ``P(m, None)`` (vocab-sharded)
lm_head     [d, V]         ``P(None, m)``
attn        wq/wk/wv       ``P(..., None, m)`` (head-sharded)
attn        wo             ``P(..., m, None)``
attn        bq/bk/bv       ``P(..., m)``
mlp         wi/wg          ``P(..., None, m)``
mlp         wo             ``P(..., m, None)``
moe         wi/wg/wo       tensor-parallel inside each expert
                           (``P(..., None, None, m)``, ``wo``
                           ``P(..., None, m, None)``) where every
                           slice of the width stays at least
                           ``EXPERT_SLICE`` (1024) wide; else
                           expert-parallel ``P(..., m, None, None)``
                           when the model-axis size divides the
                           storage expert count (virtual split
                           included); else tensor-parallel
moe         router         replicated
ssm         in_proj        ``P(..., None, m)``
ssm         out_proj       ``P(..., m, None)``
rec (the    wx/wgate/w_a/  ``P(..., None, m)``
RG-LRU      w_i
block key)  out_proj       ``P(..., m, None)``
norms etc.  *              replicated
==========  =============  ========================================

With ``fsdp=True``, tensors at or above ``fsdp_min_size`` elements
additionally shard one free, data-divisible dimension over the data
axes (never the stacked scan dim); small tensors stay replicated.
ZeRO-1 reuses the same helper (``_add_fsdp``) to scatter replicated
optimizer moments.

Paged-cache placement (``ShardingPolicy.page_spec``)
====================================================

Paged decode-cache pools (``[n_pages, page_size, ...]`` — see
:mod:`repro.serve.paging`) have no batch dimension; the *page* dim is
the capacity dim, so it takes the data axes the contiguous cache put on
batch — but only when the pool page count is provably divisible
(pjit argument shardings do not pad).  KV heads / state channels keep
the model axis per the serving rules in ``repro.serve.engine
.cache_specs``; block tables replicate (tiny int32 indirection state
every device needs to resolve its page gathers).
"""
from repro.dist.axisenv import AxisEnv, axis_env, constrain, current_env
from repro.dist.sharding import ShardingPolicy, batch_specs, param_specs

__all__ = [
    "AxisEnv", "axis_env", "constrain", "current_env",
    "ShardingPolicy", "batch_specs", "param_specs",
]
