"""Operation and byte counts on known shapes, and the roofline share."""
import json

import pytest

from bench.counts import (layer_weights, model_flops, paged_attention_need,
                          roofline_share)
from bench.spec import BENCH_DIR
from bench.weights import dims_of

QWEN = dims_of(json.loads((BENCH_DIR / "configs" /
                           "qwen1.5-0.5b.json").read_text()))
#: Mixtral-8x22B's published widths, two layers
MIX = dims_of({"hidden_size": 6144, "num_attention_heads": 48,
               "num_key_value_heads": 8, "num_hidden_layers": 2,
               "intermediate_size": 16384, "vocab_size": 32768,
               "num_local_experts": 8, "num_experts_per_tok": 2,
               "tie_word_embeddings": False, "rope_theta": 1e6,
               "rms_norm_eps": 1e-5, "initializer_range": 0.02,
               "torch_dtype": "bfloat16"})


def test_paged_attention_counts_live_context_only():
    # one step, two live slots of 100 and 28 tokens: 128 rows of K and V
    # of 16 heads x 64 per layer, q and out of 1024 each per slot
    flops, nbytes = paged_attention_need(QWEN, [[100, 28]])
    assert nbytes == 24 * 2 * (2 * 128 * 1024 + 2 * 2 * 1024)
    assert flops == 24 * 4 * 128 * 1024


def test_layer_weights_at_published_widths():
    # qwen1.5-0.5b: 4 x 1024^2 attention + 3 x 1024 x 2816 MLP
    assert layer_weights(QWEN) == 4 * 1024 ** 2 + 3 * 1024 * 2816
    # mixtral: q, o 6144^2; k, v 6144 x 1024; 2 of 8 experts; router
    assert layer_weights(MIX) == (2 * 6144 ** 2 + 2 * 6144 * 1024
                                  + 2 * 3 * 6144 * 16384 + 6144 * 8)


def test_model_flops_of_one_prefill_and_one_step():
    w = 2.0 * 24 * layer_weights(QWEN)
    head = 2.0 * 1024 * 151936
    att = 4.0 * 1024 * 24
    got = model_flops(QWEN, [3], [[4, 9]])
    assert got == pytest.approx(3 * w + head + att * 6
                                + 2 * (w + head) + att * 13)


def test_roofline_share_and_its_bound():
    share, bound = roofline_share(1e9, 819e6, 0.002, 197e12, 819e9)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = roofline_share(197e12, 1.0, 2.0, 197e12, 819e9)
    assert bound == "compute" and share == pytest.approx(50.0)
    with pytest.raises(ValueError):
        roofline_share(1e9, 819e9, 0.5, 197e12, 819e9)     # 200%: not clipped


def test_peak_table_has_the_v5e_and_refuses_other_devices():
    from bench.spec import Bench
    b = Bench(BENCH_DIR.parent, spec={})
    assert b.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        b.peaks("TPU v4")
