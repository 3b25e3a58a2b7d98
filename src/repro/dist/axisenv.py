"""Axis environment: logical dimension tags -> mesh axes.

Model code tags array dimensions with ``"B"`` / ``"S"`` / ``"M"``
(batch / sequence / model) instead of naming mesh axes; the active
:class:`AxisEnv` — installed by ``with axis_env(...):`` around the
traced computation — resolves tags to the mesh axes of the current
sharding policy.  See the package docstring for the dedup semantics.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["AxisEnv", "axis_env", "current_env", "constrain",
           "model_shard", "reduce_model"]

# A tag target: no sharding, one mesh axis, or several mesh axes.
Axes = Union[None, str, Tuple[str, ...]]

_UNSET = object()


def _tup(axes: Axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


class AxisEnv:
    """Binding of the logical tags to mesh axes (plus the mesh itself).

    ``batch`` / ``seq`` / ``model`` keep their raw form (``None`` means
    "unsharded", which callers test with ``env.seq is not None``).

    ``manual`` (``(axis name, size)`` or ``None``): the code runs inside
    a ``shard_map`` body mapped over that mesh axis, on each device's
    share of the model (its heads, its expert share, its vocabulary
    slice); the layers reduce their partial outputs over it
    (:func:`reduce_model`).
    """

    def __init__(self, batch: Axes, model: Axes, seq: Axes,
                 mesh: Optional[Mesh],
                 manual: Optional[Tuple[str, int]] = None):
        self.batch = batch
        self.model = model
        self.seq = seq
        self.mesh = mesh
        self.manual = manual

    def axes(self, tag: Optional[str]) -> Tuple[str, ...]:
        """Mesh axes a tag resolves to (only axes present on the mesh)."""
        raw = _tup({"B": self.batch, "S": self.seq, "M": self.model,
                    None: None}[tag])
        if self.mesh is None:
            return raw
        return tuple(a for a in raw if a in self.mesh.axis_names)

    def size(self, tag: Optional[str]) -> Optional[int]:
        """Total mesh extent of a tag, or None if unbound/unmeshed."""
        if self.mesh is None:
            return None
        axes = self.axes(tag)
        if not axes:
            return None
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        n = 1
        for a in axes:
            n *= int(sizes[a])
        return n


_LOCAL = threading.local()


def current_env() -> Optional[AxisEnv]:
    """The innermost active env, or None outside any ``axis_env``."""
    stack = getattr(_LOCAL, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def axis_env(policy=None, *, batch_axes: Axes = _UNSET,
             model_axis: Axes = _UNSET, seq_axis: Axes = _UNSET,
             mesh: Optional[Mesh] = None,
             manual: Optional[Tuple[str, int]] = None):
    """Install an :class:`AxisEnv` for the dynamic extent of the block.

    Accepts either a :class:`~repro.dist.sharding.ShardingPolicy`
    (positional) or explicit ``batch_axes`` / ``model_axis`` /
    ``seq_axis`` kwargs; explicit kwargs override the policy's fields
    (including an explicit ``None``, which unbinds the tag).
    ``manual``: see :class:`AxisEnv`.
    """
    if policy is not None:
        batch = policy.data_axes if batch_axes is _UNSET else batch_axes
        model = policy.model_axis if model_axis is _UNSET else model_axis
        seq = policy.seq_axis if seq_axis is _UNSET else seq_axis
    else:
        batch = None if batch_axes is _UNSET else batch_axes
        model = None if model_axis is _UNSET else model_axis
        seq = None if seq_axis is _UNSET else seq_axis
    env = AxisEnv(batch, model, seq, mesh, manual)
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    stack.append(env)
    try:
        yield env
    finally:
        stack.pop()


def constrain(x, *tags: Optional[str]):
    """``with_sharding_constraint`` by tag; identity outside any env.

    Each positional tag shards one leading dimension of ``x``
    (trailing dimensions default to unsharded).  Mesh axes are consumed
    left to right: an axis grabbed by an earlier dimension is dropped
    from later tags, and a tag with no axes left resolves to ``None``
    — so repeated tags dedup instead of building an invalid spec.
    """
    env = current_env()
    if env is None or env.mesh is None:
        return x
    used = set()
    entries = []
    for t in tags:
        free = tuple(a for a in env.axes(t) if a not in used)
        used.update(free)
        if not free:
            entries.append(None)
        elif len(free) == 1:
            entries.append(free[0])
        else:
            entries.append(free)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(env.mesh, P(*entries)))


def model_shard() -> Optional[Tuple[str, int]]:
    """``(axis name, size)`` of the manual model axis the code runs
    under, or ``None`` (whole model, or GSPMD-placed)."""
    env = current_env()
    return env.manual if env is not None else None


def reduce_model(x):
    """Sum a layer's partial output over the manual model axis, in
    float32, returned in ``x``'s dtype (the identity without one)."""
    shard = model_shard()
    if shard is None:
        return x
    return jax.lax.psum(x.astype(jnp.float32), shard[0]).astype(x.dtype)
