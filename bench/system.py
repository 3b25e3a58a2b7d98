"""The system under test, as the benchmark drives it.

Everything here that touches the program goes through its public
surface: a :class:`repro.models.config.ModelConfig` built from the
configuration file, the program's parameter tree filled with the
benchmark's own seeded tensors (:mod:`bench.weights`) in one jitted
call, and :class:`repro.serve.ServeEngine` with the paged cache and the
``pallas_paged`` decode kernel.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import (Dims, dims_of, is_norm, name_word, seed_words,
                           tensor)

__all__ = ["model_config", "build", "weights", "warm_lengths"]


def model_config(cfgj: dict):
    """The program's ``ModelConfig`` for a configuration file: the repo's
    architecture entry with every size taken from the file.  Attention is
    global wherever the file's ``sliding_window`` is null."""
    from repro.configs import get_config
    dm = dims_of(cfgj)
    base = get_config(cfgj["repo_arch"])
    window = cfgj.get("sliding_window") if cfgj.get("use_sliding_window",
                                                    True) else None
    return dataclasses.replace(
        base, name=cfgj["name"], n_layers=dm.layers, d_model=dm.d,
        n_heads=dm.heads, n_kv_heads=dm.kv_heads, head_dim=dm.head_dim,
        d_ff=dm.ff, vocab_size=dm.vocab, rope_theta=dm.rope_theta,
        tie_embeddings=dm.tied, qkv_bias=dm.qkv_bias,
        attn_pattern=("local",) if window else ("global",),
        pattern_tail=(), window_size=window or None,
        n_experts=dm.experts, experts_per_token=dm.top_k,
        mlp_gated=True, mlp_activation="silu", dtype=dm.dtype_name)


def _scan_tensors(dm: Dims, words, names, shapes, reshape=None):
    """Tensors ``names[i][k]`` (shape ``shapes[k]``) for every row ``i``,
    stacked on a leading axis by a scan, one row at a time, so that the
    stacked leaves are written in place and no row is held twice.
    ``reshape[k]`` (optional) reshapes a tensor inside the row."""
    nw = np.asarray([[name_word(n) for n in row] for row in names],
                    np.uint32)
    norms = [is_norm(n) for n in names[0]]

    def body(carry, w):
        out = []
        for k, shape in enumerate(shapes):
            x = tensor(dm, words, w[k], shape, norms[k])
            if reshape is not None and reshape[k] is not None:
                x = reshape[k](x)
            out.append(x)
        return carry, tuple(out)

    return jax.lax.scan(body, 0, jnp.asarray(nw))[1]


def _param_tree(dm: Dims, cfg, words):
    """The program's parameter tree (``TransformerLM.init`` layout),
    every leaf a :func:`bench.weights.tensor` by name."""
    L, d, f = dm.layers, dm.d, dm.ff
    hq, hkv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    keys = ["ln1", "ln2", "q", "k", "v", "o"]
    shapes = [(d,), (d,), (d, hq), (d, hkv), (d, hkv), (hq, d)]
    if dm.qkv_bias:
        keys += ["bq", "bk", "bv"]
        shapes += [(hq,), (hkv,), (hkv,)]
    if not dm.experts:
        keys += ["gate", "up", "down"]
        shapes += [(d, f), (d, f), (f, d)]
    else:
        keys += ["router"]
        shapes += [(d, dm.experts)]
    got = dict(zip(keys, _scan_tensors(
        dm, words, [[f"l{i}.{k}" for k in keys] for i in range(L)], shapes)))
    attn = {"wq": got["q"], "wk": got["k"], "wv": got["v"], "wo": got["o"]}
    if dm.qkv_bias:
        attn |= {"bq": got["bq"], "bk": got["bk"], "bv": got["bv"]}
    layer = {"ln1": {"scale": got["ln1"]}, "attn": attn,
             "ln2": {"scale": got["ln2"]}}
    if dm.experts:
        # each expert is stored as `vs` virtual experts of width f/vs
        # (the program's layout; exact for a gated MLP), made one expert
        # of one layer per scan step
        vs, E = cfg.moe_virtual_split, dm.experts
        split_in = lambda x: x.reshape(d, vs, f // vs).transpose(1, 0, 2)
        split_out = lambda x: x.reshape(vs, f // vs, d)
        gate, up, down = _scan_tensors(
            dm, words, [[f"l{i}.e{j}.{k}" for k in ("gate", "up", "down")]
                        for i in range(L) for j in range(E)],
            [(d, f), (d, f), (f, d)], [split_in, split_in, split_out])
        layer["moe"] = {
            "router": got["router"].astype(jnp.float32),
            "wi": up.reshape(L, E * vs, d, f // vs),
            "wg": gate.reshape(L, E * vs, d, f // vs),
            "wo": down.reshape(L, E * vs, f // vs, d)}
    else:
        layer["mlp"] = {"wi": got["up"], "wg": got["gate"],
                        "wo": got["down"]}
    params = {"embed": {"tok": _named(dm, words, "embed", (dm.vocab, d))},
              "blocks": (layer,),
              "final_norm": {"scale": _named(dm, words, "final_norm", (d,))}}
    if not dm.tied:
        params["lm_head"] = _named(dm, words, "lm_head", (d, dm.vocab))
    return params


def _named(dm, words, name, shape):
    return tensor(dm, words, name_word(name), shape, is_norm(name))


def _check_layout(ours, theirs) -> None:
    a = jax.tree_util.tree_flatten_with_path(ours)[0]
    b = jax.tree_util.tree_flatten_with_path(theirs)[0]
    sa = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in a}
    sb = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in b}
    if sa != sb:
        diff = sorted(set(sa.items()) ^ set(sb.items()), key=str)[:6]
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{diff}")


def build(cfgj: dict, seed: int, devices):
    """(model, engine) for one configuration file and seed.  The weights
    are made on the device in one jitted call, in the served dtype, with
    the engine's parameter placement."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import ShardingPolicy, param_specs
    from repro.models.transformer import TransformerLM
    from repro.serve import PagedCacheConfig, ServeEngine

    eng = cfgj["engine"]
    cfg = model_config(cfgj)
    dm = dims_of(cfgj)
    model = TransformerLM(cfg)
    data, mdl = eng["mesh"]
    mesh = Mesh(np.array(devices[:data * mdl]).reshape(data, mdl),
                ("data", "model"))
    policy = ShardingPolicy.for_mesh(mesh)
    words = jnp.asarray(seed_words(seed))
    abstract = jax.eval_shape(lambda w: _param_tree(dm, cfg, w), words)
    _check_layout(abstract, jax.eval_shape(
        lambda: model.init(jax.random.key(0))))
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             param_specs(abstract, policy),
                             is_leaf=lambda x: isinstance(x, P))
    params = weights(cfgj, cfg, seed, shardings)
    engine = ServeEngine(
        model, params, max_len=eng["max_len"], max_batch=eng["slots"],
        mesh=mesh, decode_backend=eng["decode_backend"],
        paged=PagedCacheConfig(page_size=eng["page_size"]))
    return model, engine


_WEIGHT_FNS: dict = {}


def weights(cfgj: dict, cfg, seed: int, shardings):
    """The program's parameter tree for ``seed``, made on the device in
    one jitted call (compiled once per configuration)."""
    dm = dims_of(cfgj)
    fn = _WEIGHT_FNS.get((dm, cfg))
    if fn is None:
        fn = _WEIGHT_FNS[(dm, cfg)] = jax.jit(
            lambda w: _param_tree(dm, cfg, w), out_shardings=shardings)
    return fn(jnp.asarray(seed_words(seed)))


def warm_lengths(ladder, lengths, page_size: int, max_len: int) -> list:
    """One prompt length inside each prefill bucket that ``lengths`` can
    reach (the engine pads a prompt up to the smallest rung that fits).
    Each is a whole number of pages where the bucket allows, so that the
    first decode step after it assigns a new page, as the window's will."""
    out, lo = [], 0
    for b in ladder:
        if any(lo < n <= b for n in lengths):
            top = min(b, max_len - 2) // page_size * page_size
            out.append(top if top > lo else lo + 1)
        lo = b
    return out

