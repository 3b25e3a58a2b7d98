"""Seeded weights, named by the benchmark and not by the program.

Every tensor of a configuration has a name of its own (``embed``,
``l3.q``, ``l0.e5.up``, ...) and its values are a pure function of
``(seed, name, shape)``: uniform bits from a key folded from both, scaled
to the published ``initializer_range`` as a standard deviation, and
rounded once to the dtype the configuration is served in.  So the
harness can build the program's whole parameter tree in one jitted call,
and the plain reference can regenerate any one tensor on its own, layer
by layer or expert by expert, and get the same values bit for bit.

Matrices are stored ``[in, out]`` (``x @ w``).  Names, per layer ``l``:

* attention: ``l.q`` ``l.k`` ``l.v`` ``l.o``; with QKV bias also
  ``l.bq`` ``l.bk`` ``l.bv``; norms ``l.ln1`` ``l.ln2``;
* dense gated MLP: ``l.gate`` ``l.up`` ``l.down``;
* sparse experts: ``l.router`` ``[d, E]`` and ``l.e<j>.gate`` /
  ``.up`` / ``.down`` for each expert ``j``;
* ``embed`` ``[vocab, d]``, ``final_norm``, and ``lm_head`` ``[d, vocab]``
  unless the embeddings are tied.
"""
from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Dims", "dims_of", "is_norm", "name_word", "seed_words", "tensor",
           "tensor_names"]

#: a norm scale is 1 + U(-NORM_SPREAD, NORM_SPREAD): not all ones, so a
#: norm applied to the wrong tensor, or not at all, shows
NORM_SPREAD = 0.1


@dataclasses.dataclass(frozen=True)
class Dims:
    """Sizes of one configuration file, under short names (hashable, so
    jitted functions can take it as a static argument)."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int
    top_k: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    eps: float
    std: float
    dtype_name: str

    @property
    def dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            self.dtype_name]


def dims_of(c: dict) -> Dims:
    """:class:`Dims` of a configuration file (published key names)."""
    d, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
    return Dims(
        layers=int(c["num_hidden_layers"]), d=d, heads=heads,
        kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim") or d // heads),
        ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
        experts=int(c.get("num_local_experts") or 0),
        top_k=int(c.get("num_experts_per_tok") or 0),
        qkv_bias=bool(c.get("qkv_bias", False)),
        tied=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        std=float(c["initializer_range"]), dtype_name=c["torch_dtype"])


def tensor_names(dm: Dims, layer: int) -> dict:
    """``{name: shape}`` of one layer's tensors."""
    d, hq, hkv = dm.d, dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    p = f"l{layer}."
    out = {p + "ln1": (d,), p + "ln2": (d,),
           p + "q": (d, hq), p + "k": (d, hkv), p + "v": (d, hkv),
           p + "o": (hq, d)}
    if dm.qkv_bias:
        out |= {p + "bq": (hq,), p + "bk": (hkv,), p + "bv": (hkv,)}
    if dm.experts:
        out[p + "router"] = (d, dm.experts)
        for j in range(dm.experts):
            out |= {f"{p}e{j}.gate": (d, dm.ff), f"{p}e{j}.up": (d, dm.ff),
                    f"{p}e{j}.down": (dm.ff, d)}
    else:
        out |= {p + "gate": (d, dm.ff), p + "up": (d, dm.ff),
                p + "down": (dm.ff, d)}
    return out


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (low, high)."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def name_word(name: str) -> np.uint32:
    return np.uint32(zlib.crc32(name.encode()))


def is_norm(name: str) -> bool:
    return name.rsplit(".", 1)[-1] in ("ln1", "ln2", "final_norm")


def tensor(dm: Dims, words, nameword, shape, norm: bool) -> jnp.ndarray:
    """One tensor in the served dtype, from the seed's ``words`` and the
    tensor's ``name_word`` (both may be traced, so one compiled program
    makes every tensor of a shape).  ``norm``: a norm scale."""
    k = jax.random.key(words[0])
    k = jax.random.fold_in(k, words[1])
    k = jax.random.fold_in(k, nameword)
    u = jax.random.uniform(k, tuple(shape), jnp.float32, -1.0, 1.0)
    if norm:
        x = 1.0 + NORM_SPREAD * u
    else:
        x = (dm.std * 3.0 ** 0.5) * u        # uniform with std = dm.std
    return x.astype(dm.dtype)

