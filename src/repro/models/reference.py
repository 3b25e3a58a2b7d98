"""Plain float32 reference forward of the attention-and-experts decoder
(Mixtral's block), on the program's own parameter tree.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
one sequence at a time, every layer in a Python loop: embedding; per
layer ``x + attn(rmsnorm(x))`` then ``x + ffn(rmsnorm(x))``; final
rmsnorm and the head.  Attention is causal grouped-query attention with
rotary embeddings (rotate-half form, ``theta ** (-2i / head_dim)``);
query head ``h`` reads KV head ``h // (heads / kv_heads)``.  The expert
layer routes every token with ``softmax(x @ router)``, keeps the top
``k`` probabilities renormalised to sum to one, and adds each chosen
expert's SwiGLU MLP ``down(silu(gate(x)) * up(x))`` at that weight; an
expert is computed whole, for every token, and weighted 0 where it was
not chosen.  A dense model's MLP is the same SwiGLU.

Departures from the published model, each also the program's: weights
are the program's seeded tree, not a checkpoint; an expert's matrices
are read from the program's storage layout (``moe_virtual_split``
slices of its width, concatenated back here); ``local`` layers mask
keys older than ``window_size`` (Mixtral-8x22B publishes no window, and
the tests use global layers).  No kernel, cache, padding or batching.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

__all__ = ["forward", "expert_layer"]


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


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


def _attention(p, cfg: ModelConfig, h, kind):
    s = h.shape[0]
    hd = cfg.resolved_head_dim
    q = h @ p["wq"]
    k = h @ p["wk"]
    v = h @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(s, cfg.n_heads, hd), cfg.rope_theta)
    k = _rope(k.reshape(s, cfg.n_kv_heads, hd), cfg.rope_theta)
    v = v.reshape(s, cfg.n_kv_heads, hd)
    g = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    qi, kj = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = kj <= qi
    if kind == "local" and cfg.window_size is not None:
        mask &= kj > qi - cfg.window_size
    sc = jnp.where(mask[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)
    return o.reshape(s, -1) @ p["wo"]


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def expert_layer(p, cfg: ModelConfig, h):
    """The expert layer on ``h`` [tokens, d] (float32), every expert
    whole: ``p`` holds ``router`` and the storage-layout ``wi`` / ``wg``
    / ``wo``."""
    p = _f32(p)
    e, k, vs = cfg.n_experts, cfg.experts_per_token, cfg.moe_virtual_split
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(h @ p["router"], axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(h.shape[0])[:, None], idx].set(top)
        y = jnp.zeros_like(h)
        for j in range(e):
            rows = slice(j * vs, (j + 1) * vs)
            up = jnp.concatenate(list(p["wi"][rows]), axis=-1)
            gate = jnp.concatenate(list(p["wg"][rows]), axis=-1)
            down = jnp.concatenate(list(p["wo"][rows]), axis=0)
            y = y + gates[:, j:j + 1] * _swiglu(h, gate, up, down)
    return y


def forward(params, cfg: ModelConfig, tokens) -> np.ndarray:
    """Logits [len(tokens), vocab] float32 of one sequence."""
    if cfg.pattern_tail or cfg.attn_softcap or cfg.logit_softcap:
        raise ValueError("the reference has no pattern tail or softcaps")
    params = _f32(params)
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tok"][jnp.asarray(tokens)]
        if cfg.scale_embeddings:
            x = x * cfg.d_model ** 0.5
        for i in range(cfg.n_layers):
            kind = cfg.layer_kind(i)
            if kind not in ("global", "local"):
                raise ValueError(f"no reference for {kind!r} layers")
            g, pos = divmod(i, cfg.pattern_period)
            p = jax.tree.map(lambda a: a[g], params["blocks"][pos])
            x = x + _attention(p["attn"], cfg,
                               _rmsnorm(x, p["ln1"]["scale"], eps), kind)
            h = _rmsnorm(x, p["ln2"]["scale"], eps)
            if cfg.n_experts:
                x = x + expert_layer(p["moe"], cfg, h)
            else:
                m = p["mlp"]
                x = x + _swiglu(h, m["wg"], m["wi"], m["wo"])
        x = _rmsnorm(x, params["final_norm"]["scale"], eps)
        head = (params["embed"]["tok"].T if cfg.tie_embeddings
                else params["lm_head"])
        return np.asarray(x @ head)
