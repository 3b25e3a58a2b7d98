"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU; the kernels target TPU BlockSpec tiling)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import attention
from repro.kernels.rate_match.ops import schedule_bits
from repro.kernels.refresh_sim.ops import window_update

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # b, sq, h, kvh, hd, window, softcap, dtype
    (2, 256, 4, 2, 64, None, None, np.float32),
    (1, 128, 4, 1, 64, 64, 50.0, np.float32),
    (2, 256, 8, 8, 32, None, 30.0, np.float32),
    (1, 512, 2, 2, 128, 128, None, np.float32),
    (1, 256, 6, 3, 64, None, None, np.float32),
    (2, 128, 4, 4, 64, 32, None, jnp.bfloat16),
    (1, 256, 4, 2, 256, None, 50.0, np.float32),
]


@pytest.mark.parametrize(
    "b,sq,h,kvh,hd,window,softcap,dtype", ATTN_CASES)
def test_flash_attention_matches_oracle(b, sq, h, kvh, hd, window, softcap,
                                        dtype, rng):
    q = rng.standard_normal((b, sq, h, hd)).astype(np.float32)
    k = rng.standard_normal((b, sq, kvh, hd)).astype(np.float32)
    v = rng.standard_normal((b, sq, kvh, hd)).astype(np.float32)
    q, k, v = (jnp.asarray(x, dtype) for x in (q, k, v))
    ref = attention(q, k, v, causal=True, window=window, softcap=softcap,
                    backend="ref")
    pal = attention(q, k, v, causal=True, window=window, softcap=softcap,
                    backend="pallas")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(pal, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


# Edge cases (PR 5): sequences that do NOT tile the block grid (the
# kernel pads to the grid and slices back, masking padded keys via
# kv_len) and sliding windows smaller than one tile (the band lives
# entirely inside single blocks; the block-level early exit must not
# skip them).
ATTN_EDGE_CASES = [
    # b, sq, h, kvh, hd, q_blk, kv_blk, window, softcap
    (1, 160, 4, 2, 32, 64, 64, None, None),    # sq % q_block != 0
    (2, 200, 4, 4, 16, 128, 128, 16, 30.0),    # pad + window < one tile
    (1, 100, 2, 1, 16, 64, 64, 1, None),       # window=1: self-only band
    (1, 130, 4, 2, 16, 64, 512, None, 50.0),   # kv_block > seq, pad q
    (2, 96, 4, 2, 16, 64, 32, 24, None),       # window < kv tile, pad q
    (1, 33, 2, 2, 8, 32, 32, 40, None),        # window > seq (no-op band)
]


@pytest.mark.parametrize(
    "b,sq,h,kvh,hd,qb,kb,window,softcap", ATTN_EDGE_CASES)
def test_flash_attention_edge_tiling(b, sq, h, kvh, hd, qb, kb, window,
                                     softcap, rng):
    from repro.kernels.flash_attention.kernel import flash_attention
    q = jnp.asarray(rng.standard_normal((b, sq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sq, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sq, kvh, hd)), jnp.float32)
    ref = attention(q, k, v, causal=True, window=window, softcap=softcap,
                    backend="ref")
    pal = flash_attention(q, k, v, causal=True, window=window,
                          softcap=softcap, q_block=qb, kv_block=kb)
    assert pal.shape == ref.shape      # padding sliced back off
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_vs_model_blocked_path(rng):
    """The model's blocked-jnp attention and the Pallas kernel agree."""
    from repro.models.attention import attn_apply, attn_init
    from repro.models.config import ModelConfig
    import jax
    cfg = ModelConfig("t", "dense", 2, 64, 4, 2, 128, 256, head_dim=16,
                      dtype="float32", window_size=128,
                      attn_pattern=("local",))
    params = attn_init(jax.random.key(0), cfg, jnp.float32)
    # compare raw sdpa path: extract q/k/v through the kernel op
    x = rng.standard_normal((2, 256, 64)).astype(np.float32)
    # model path (includes projections + rope) — just ensure it runs on
    # a >2*QBLOCK sequence exercising the blocked branch
    from repro.models import attention as A
    old = A.QBLOCK
    A.QBLOCK = 64
    try:
        pos = jnp.broadcast_to(jnp.arange(256), (2, 256))
        out_blocked = attn_apply(params, cfg, jnp.asarray(x), pos, "local")
        A.QBLOCK = 4096  # force direct path
        out_direct = attn_apply(params, cfg, jnp.asarray(x), pos, "local")
    finally:
        A.QBLOCK = old
    np.testing.assert_allclose(np.asarray(out_blocked),
                               np.asarray(out_direct), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# refresh_sim kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_rows", [8192, 16384, 20000])
@pytest.mark.parametrize("skip", [0, 1])
def test_refresh_window_update_matches_ref(n_rows, skip, rng):
    age = jnp.asarray(rng.integers(0, 2, n_rows), jnp.int32)
    args = dict(acc_start=100, acc_len=700, alloc_lo=50, alloc_hi=5000,
                ref_lo=0, ref_hi=n_rows, skip_accessed=skip)
    a = window_update(age, backend="ref", **args)
    b = window_update(age, backend="pallas", **args)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    for x, y in zip(a[1:], b[1:]):
        assert int(x) == int(y)


# ---------------------------------------------------------------------------
# rate_match kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("na,nr,length", [
    (2, 4, 64), (3, 5, 100), (128, 1024, 2048), (0, 7, 16),
    (1_000_000, 4_194_304, 4096),
])
def test_rate_match_kernel_matches_ref(na, nr, length):
    a = np.asarray(schedule_bits(na, nr, length, backend="ref"))
    b = np.asarray(schedule_bits(na, nr, length, backend="pallas"))
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# grouped matmul
# ---------------------------------------------------------------------------
GMM_CASES = [
    # m, k, n, group sizes, held offset, held count, layers, out dtype
    (128, 64, 128, (10, 0, 20, 5, 30, 7, 0, 6), 0, 8, 1, None),
    (200, 64, 128, (10, 0, 20, 5, 30, 7, 0, 6), 2, 4, 3, None),
    (600, 128, 256, (100, 150, 0, 250, 40, 10, 30, 20), 4, 4, 2, None),
    (700, 64, 128, (300, 0, 200, 100), 0, 4, 1, jnp.float32),
    (64, 64, 128, (0, 0, 64, 0), 1, 2, 2, None),
]


@pytest.mark.parametrize("m,k,n,sizes,offset,held,layers,out_dtype",
                         GMM_CASES)
def test_grouped_matmul_matches_oracle(m, k, n, sizes, offset, held, layers,
                                       out_dtype, rng):
    """The held groups' rows (groups sharing a row tile, groups over
    several tiles, empty groups, a layer of a stack) match the oracle; rows of
    groups held elsewhere and rows past the routed ones are the
    caller's to drop."""
    from repro.kernels.grouped_matmul.ops import grouped_matmul
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((layers, held, k, n)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    layer = layers - 1
    got = grouped_matmul(lhs, rhs, gs, offset, layer, out_dtype=out_dtype)
    want = grouped_matmul(lhs, rhs, gs, offset, layer, out_dtype=out_dtype,
                          backend="ref")
    assert got.dtype == want.dtype == (out_dtype or jnp.float32)
    ends = np.cumsum(sizes)
    rows = np.arange(m)
    mine = ((rows >= (ends[offset] - sizes[offset]))
            & (rows < ends[offset + held - 1]))
    assert mine.sum() == sum(sizes[offset:offset + held])
    np.testing.assert_allclose(np.asarray(got)[mine], np.asarray(want)[mine],
                               rtol=1e-5, atol=1e-4)
