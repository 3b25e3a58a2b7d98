"""mixtral-8x22b [moe] — arXiv:2401.04088 (hf: mistralai/Mixtral-8x22B).

56L d_model=6144 48H (GQA kv=8) d_ff=16384 vocab=32768, MoE 8 experts
top-2, SwiGLU, RMSNorm eps 1e-5, rope theta 1e6.  The published
``sliding_window`` is null: every layer attends globally.  This entry
keeps ``("local",)`` layers with a 4096 window as the repo's windowed
test architecture (every layer's KV cache bounded, so ``long_500k``
runs with a ring cache); the chip benchmark serves the published
global attention (``bench/system.model_config``).
"""
from repro.models.config import ModelConfig

ARCH = "mixtral-8x22b"


def full_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH, family="moe",
        n_layers=56, d_model=6144, n_heads=48, n_kv_heads=8,
        d_ff=16384, vocab_size=32768, head_dim=128,
        mlp_gated=True, mlp_activation="silu",
        attn_pattern=("local",), window_size=4096,
        n_experts=8, experts_per_token=2,
        # virtual split 2 -> 16 storage experts: exact layout transform
        # targeting the 16-way production model axis (see ModelConfig)
        moe_virtual_split=2,
        tie_embeddings=False, rope_theta=1e6, rms_norm_eps=1e-5,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH + "-smoke", family="moe",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=256, head_dim=16,
        mlp_gated=True, mlp_activation="silu",
        attn_pattern=("local",), window_size=8,
        n_experts=4, experts_per_token=2,
        tie_embeddings=False, dtype="float32", rms_norm_eps=1e-5,
    )
