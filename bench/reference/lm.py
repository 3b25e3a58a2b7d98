"""Plain float32 reference of the benchmark's decoder LMs.

Straight ``jax.numpy``, no kernels, no cache, no batching tricks:

* dense (Qwen1.5 / Qwen2 architecture): pre-norm RMSNorm, rotary
  embeddings (rotate-half form), multi-head attention with Q/K/V bias,
  SwiGLU MLP ``down(silu(gate(x)) * up(x))``, tied or separate output
  head;
* sparse (Mixtral architecture): the same block with grouped-query
  attention, no bias, and in place of the MLP a router ``softmax(x @ W)``
  whose top-k probabilities, renormalised to sum to one, weight the
  chosen experts.  Every expert is a SwiGLU MLP computed exactly; an
  expert that a token did not choose gets weight 0 for it.

Attention is global and causal (Mixtral-8x22B's published
``sliding_window`` is null).  Every matrix product runs at
``jax.default_matmul_precision("highest")``.  Weights are regenerated by
name from the seed (:mod:`bench.weights`) one layer, or one expert, at a
time, so the reference fits beside nothing else on one chip.

``quant="fp8"`` is the control: the same computation with both operands
of every weight matrix product rounded to float8 e4m3, with a scale per
row of the activations and per output column of the weights.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import (Dims, is_norm, name_word, seed_words, tensor,
                           tensor_names)

__all__ = ["final_logits", "served_rows"]

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (a / s).astype(_F8).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [s, heads, hd] at positions 0..s-1."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _attention_block(x, w, dm: Dims, quant):
    """x: [n, L, d] -> x + attention(ln1(x)), causal per sequence."""
    g = dm.heads // dm.kv_heads

    def one(xs):
        h = _rmsnorm(xs, w["ln1"], dm.eps)
        q = _mm(h, w["q"], quant)
        k = _mm(h, w["k"], quant)
        v = _mm(h, w["v"], quant)
        if dm.qkv_bias:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        s = xs.shape[0]
        q = _rope(q.reshape(s, dm.heads, dm.head_dim), dm.rope_theta)
        k = _rope(k.reshape(s, dm.kv_heads, dm.head_dim), dm.rope_theta)
        v = v.reshape(s, dm.kv_heads, dm.head_dim)
        k = jnp.repeat(k, g, axis=1)           # query head i uses kv i // g
        v = jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k) * dm.head_dim ** -0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(s, -1)
        return xs + _mm(o, w["o"], quant)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _mlp(h, w, dm: Dims, quant):
    a = jax.nn.silu(_mm(h, w["gate"], quant)) * _mm(h, w["up"], quant)
    return _mm(a, w["down"], quant)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _route(h, router, dm: Dims, quant):
    """Gate weight of every expert for every token: [T, E], zero where
    the expert is not among the token's top k; and each token's routing
    margin, the k-th largest probability less the next one."""
    probs = jax.nn.softmax(_mm(h, router, quant), axis=-1)
    top, idx = jax.lax.top_k(probs, dm.top_k + 1)
    margin = top[:, dm.top_k - 1] - top[:, dm.top_k]
    top, idx = top[:, :dm.top_k], idx[:, :dm.top_k]
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], idx].set(top)
    return gates, margin


@functools.partial(jax.jit, static_argnames=("dm",))
def _ln(x, scale, dm: Dims):
    return _rmsnorm(x, scale, dm.eps)


@functools.partial(jax.jit, static_argnames=("dm", "quant"))
def _head(x, scale, head, dm: Dims, quant):
    return _mm(_rmsnorm(x, scale, dm.eps), head, quant)


@functools.partial(jax.jit, static_argnames=("dm", "shapes"))
def _gen(words, namewords, dm: Dims, shapes):
    return tuple(tensor(dm, words, namewords[i], s, norm).astype(jnp.float32)
                 for i, (s, norm) in enumerate(shapes))


def _weights(dm: Dims, seed: int, names) -> dict:
    """Regenerate tensors by name, upcast to float32.  The names ride in
    as data, so one compiled program serves every layer (or expert)."""
    items = sorted(names.items())
    out = _gen(jnp.asarray(seed_words(seed)),
               jnp.asarray([name_word(n) for n, _ in items]), dm,
               tuple((tuple(s), is_norm(n)) for n, s in items))
    return {n.rsplit(".", 1)[-1]: a for (n, _), a in zip(items, out)}


def final_logits(dm: Dims, seed: int, seqs: Sequence[np.ndarray],
                 rows: Sequence[np.ndarray], length: int,
                 quant: Optional[str] = None, margins: bool = False):
    """Logits, float32, at ``rows[i]`` (positions) of sequence ``seqs[i]``,
    stacked over all sequences in order: ``[sum(len(rows)), vocab]``.

    Every sequence is padded to ``length`` (the engine's context), so a
    configuration's programs have one shape and compile once.  With
    ``margins`` (sparse experts) also return, per row, the smallest
    routing margin over the layers (:func:`_route`): how near the
    reference's own top-k choice came to a tie there."""
    with jax.default_matmul_precision("highest"):
        logits, margin = _forward(dm, seed, seqs, rows, length, quant)
    return (logits, margin) if margins else logits


def _forward(dm, seed, seqs, rows, L, quant):
    n = len(seqs)
    if max(len(s) for s in seqs) > L:
        raise ValueError(f"a sequence is longer than {L}")
    # padding only trails, so causality keeps it out of every real
    # position; it is computed with the rest, to keep one shape
    tok = np.zeros((n, L), np.int32)
    for i, s in enumerate(seqs):
        tok[i, :len(s)] = s
    want = np.concatenate([i * L + np.asarray(r) for i, r in enumerate(rows)])

    embed = _weights(dm, seed, {"embed": (dm.vocab, dm.d)})["embed"]
    x = embed[jnp.asarray(tok)]                  # [n, L, d]
    if dm.tied:
        head = embed.T
    else:
        del embed
        head = _weights(dm, seed, {"lm_head": (dm.d, dm.vocab)})["lm_head"]
    margin = jnp.full((n * L,), jnp.inf, jnp.float32)
    for layer in range(dm.layers):
        names = tensor_names(dm, layer)
        p = f"l{layer}."
        attn = {n: s for n, s in names.items()
                if n[len(p):] in ("ln1", "q", "k", "v", "o", "bq", "bk", "bv")}
        x = _attention_block(x, _weights(dm, seed, attn), dm, quant)
        flat = x.reshape(n * L, dm.d)
        ln2 = _weights(dm, seed, {p + "ln2": names[p + "ln2"]})["ln2"]
        h = _ln(flat, ln2, dm)
        if dm.experts:
            router = _weights(dm, seed, {p + "router": names[p + "router"]})
            gates, m = _route(h, router["router"], dm, quant)
            margin = jnp.minimum(margin, m)
            y = jnp.zeros_like(h)
            for j in range(dm.experts):
                e = f"{p}e{j}."
                w = _weights(dm, seed, {e + k: names[e + k]
                                        for k in ("gate", "up", "down")})
                y = y + gates[:, j:j + 1] * _mlp(h, w, dm, quant)
                del w
        else:
            w = _weights(dm, seed, {p + k: names[p + k]
                                    for k in ("gate", "up", "down")})
            y = _mlp(h, w, dm, quant)
        x = (flat + y).reshape(n, L, dm.d)
    final = _weights(dm, seed, {"final_norm": (dm.d,)})["final_norm"]
    want = jnp.asarray(want)
    picked = x.reshape(n * L, dm.d)[want]
    return _head(picked, final, head, dm, quant), margin[want]


def served_rows(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits choose served tokens 0..n_served-1 of a
    request, in a sequence ``prompt ++ served[:-1]``."""
    return prompt_len - 1 + np.arange(n_served)

