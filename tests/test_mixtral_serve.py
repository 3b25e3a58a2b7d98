"""A Mixtral-shaped model served through ``ServeEngine`` against the plain
float32 reference (:mod:`repro.models.reference`), on one device and on
a forced four-device ``(data=1, model=4)`` CPU mesh.

The tiny model has Mixtral's block: 8 experts, top-2, SwiGLU experts
stored as 2 virtual experts each, grouped-query attention (8 query and
4 KV heads), RMSNorm epsilon 1e-5, float32 weights.  A prompt is
prefilled and then decoded through the paged cache with the Pallas
kernels in interpret mode, teacher-forced, and every logit it produces
is compared with the reference's full forward over the same sequence.
On the mesh each device holds 2 of the 8 experts (4 virtual), 2 query
and 1 KV head of each group, and a quarter of the vocabulary.

The forced device count must be set before JAX starts, so the mesh
cases run this module in a subprocess.
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import reference
from repro.models.config import ModelConfig

CFG = ModelConfig(
    "tiny-mixtral", "moe", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
    d_ff=256, vocab_size=256, head_dim=16, attn_pattern=("global",),
    n_experts=8, experts_per_token=2, moe_virtual_split=2,
    tie_embeddings=False, rope_theta=1e6, rms_norm_eps=1e-5,
    dtype="float32")
PROMPTS = (7, 4)
DECODE = 6
#: served float32 logits against the float32 reference: the two differ
#: in summation order only (the paged kernel's online softmax, the
#: grouped matmul's tiles, the sum over the model axis): 2.5e-6 on
#: logits up to about 3 (seed 0).  The same engine with bfloat16
#: weights and products, the control, misses by 0.041.
TOL = 1e-4


def _sequences(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, (n + DECODE,)).astype(np.int32)
            for n in PROMPTS]


def served_logits(cfg, params, mesh=None, backend="pallas_paged"):
    """Logits of every position the engine produced: per sequence, the
    prefill's last position then ``DECODE`` teacher-forced steps."""
    from repro.models.transformer import TransformerLM
    from repro.serve import PagedCacheConfig, ServeEngine

    engine = ServeEngine(TransformerLM(cfg), params, max_len=32,
                         max_batch=len(PROMPTS), mesh=mesh,
                         paged=PagedCacheConfig(page_size=4),
                         decode_backend=backend)
    seqs = _sequences()
    cache = engine.new_cache()
    out = [[] for _ in seqs]
    tok = np.zeros((len(seqs),), np.int32)
    pos = np.zeros((len(seqs),), np.int32)
    for s, (n, seq) in enumerate(zip(PROMPTS, seqs)):
        logits, cache, _ = engine.prefill_into(cache, s, seq[:n])
        out[s].append(np.asarray(logits[0], np.float32))
        tok[s], pos[s] = seq[n], n
    for t in range(DECODE - 1):
        for s in range(len(seqs)):
            cache, ok = engine.page_table.prepare_step(cache, s, int(pos[s]))
            assert ok
        logits, cache = engine.decode_step(cache, tok, pos)
        logits = np.asarray(logits, np.float32)
        for s, (n, seq) in enumerate(zip(PROMPTS, seqs)):
            out[s].append(logits[s])
            tok[s], pos[s] = seq[n + t + 1], pos[s] + 1
    return [np.stack(o) for o in out]


def reference_logits(cfg, params):
    seqs = _sequences()
    return [reference.forward(params, cfg, seq[:n + DECODE - 1])[n - 1:]
            for n, seq in zip(PROMPTS, seqs)]


def gap(cfg, params, mesh=None, ref_params=None, backend="pallas_paged"):
    """Widest gap between served and reference logits."""
    got = served_logits(cfg, params, mesh, backend)
    want = reference_logits(CFG, params if ref_params is None
                            else ref_params)
    return max(float(np.max(np.abs(a - b))) for a, b in zip(got, want))


def params_of(seed=0):
    from repro.models.transformer import TransformerLM
    return TransformerLM(CFG).init(jax.random.key(seed))


def bf16_control_gap(mesh=None):
    """The engine with bfloat16 weights and products, against the
    float32 reference of the same (rounded) weights."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    p16 = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
        and a.ndim > 1 and a.shape[-1] != CFG.n_experts else a, params_of())
    return gap(cfg, p16, mesh, ref_params=p16)


def share_sums(mesh):
    """Per placement, the widest difference between the sum of the four
    devices' parts of one expert layer and the reference layer."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.dist.axisenv import axis_env
    from repro.models import moe

    p = jax.tree.map(lambda a: a[0], params_of()["blocks"][0]["moe"])
    h = jnp.asarray(np.random.default_rng(1).standard_normal(
        (1, 24, CFG.d_model)), jnp.float32)
    want = np.asarray(reference.expert_layer(p, CFG, h[0]))
    specs = {"experts": {"wi": P("model"), "wg": P("model"),
                         "wo": P("model")},
             "width": {"wi": P(None, None, "model"),
                       "wg": P(None, None, "model"),
                       "wo": P(None, "model", None)}}
    out = {}
    for name, spec in specs.items():
        spec = dict(spec, router=P())

        def body(p, h):
            with axis_env(batch_axes=None, model_axis=None, seq_axis=None,
                          mesh=None, manual=("model", 4)):
                part, _ = moe.moe_share(p, CFG, h)
            return part[None]

        parts = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(spec, P()), out_specs=P("model"),
            check_vma=False))(
                jax.device_put(p, {k: NamedSharding(mesh, s)
                                   for k, s in spec.items()}), h)
        parts = np.asarray(parts)
        assert parts.shape[0] == 4
        # each device's part alone is not the layer
        assert float(np.max(np.abs(parts[0, 0] - want))) > 10 * TOL
        out[name] = float(np.max(np.abs(parts.sum(0)[0] - want)))
    return out


def _main_mesh():
    """The four-device cases, run in a subprocess: one line of results."""
    from jax.sharding import Mesh
    assert len(jax.devices()) == 4, jax.devices()
    mesh = Mesh(np.array(jax.devices()).reshape(1, 4), ("data", "model"))
    res = {"gap": gap(CFG, params_of(), mesh),
           "gather_gap": gap(CFG, params_of(), mesh, backend="gather"),
           "control": bf16_control_gap(mesh)}
    res.update(share_sums(mesh))
    print("RESULT", json.dumps(res))


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               + os.environ.get("XLA_FLAGS", ""))
    here = pathlib.Path(__file__).resolve().parent
    src = str(here.parent / "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src, str(here)] + [p for p in env.get("PYTHONPATH", "").split(
            os.pathsep) if p])
    r = subprocess.run(
        [sys.executable, "-c",
         "import test_mixtral_serve as t; t._main_mesh()"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")]
    return json.loads(line[-1][len("RESULT"):])


def test_prefill_then_decode_matches_reference():
    assert gap(CFG, params_of()) < TOL


def test_bf16_control_misses_the_tolerance():
    assert bf16_control_gap() > 20 * TOL


def test_prefill_then_decode_matches_reference_on_model_axis(mesh_results):
    """The Pallas kernel and the gather path alike read each device's
    own KV heads."""
    assert mesh_results["gap"] < TOL
    assert mesh_results["gather_gap"] < TOL
    assert mesh_results["control"] > 20 * TOL


@pytest.mark.parametrize("placement", ["experts", "width"])
def test_shares_add_up_to_the_layer(mesh_results, placement):
    """Two experts per device, or a quarter of every expert's width:
    the four parts sum to the uncut layer."""
    assert mesh_results[placement] < TOL


def test_rms_norm_eps_reaches_every_norm():
    """The configuration's epsilon is the one every RMSNorm adds: with a
    large epsilon the served logits follow the reference computed with
    it, and differ from those of the default epsilon."""
    cfg = dataclasses.replace(CFG, rms_norm_eps=0.5)
    p = params_of()
    assert gap(cfg, p, ref_params=None) > 10 * TOL   # reference at 1e-5
    got = served_logits(cfg, p)
    seqs = _sequences()
    want = [reference.forward(p, cfg, seq[:n + DECODE - 1])[n - 1:]
            for n, seq in zip(PROMPTS, seqs)]
    assert max(float(np.max(np.abs(a - b)))
               for a, b in zip(got, want)) < TOL
