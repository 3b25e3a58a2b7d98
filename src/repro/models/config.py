"""Model configuration: one dataclass drives every architecture.

Each assigned architecture is a :class:`ModelConfig` instance in
``repro.configs.<id>``; per-arch quirks (GeGLU, logit softcaps, QKV
bias, alternating local/global attention, MoE, Mamba, RG-LRU, modality
frontends) are config fields so the whole zoo shares one code path —
which is what lets the 40-cell dry-run sweep be a single driver.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

__all__ = ["ModelConfig", "LayerKind"]

# Layer kinds appearing in ``attn_pattern`` (cycled across depth):
#   "global" — full causal attention
#   "local"  — sliding-window causal attention (window_size)
#   "ssm"    — Mamba-1 selective-state-space block (attention-free)
#   "rglru"  — RG-LRU recurrent block (RecurrentGemma)
LayerKind = str


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | vlm | ssm | hybrid | audio
    n_layers: int
    d_model: int
    n_heads: int                 # query heads (0 for attention-free archs)
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    # --- MLP ---------------------------------------------------------------
    mlp_gated: bool = True       # SwiGLU/GeGLU vs plain 2-layer MLP
    mlp_activation: str = "silu"  # silu | gelu
    # --- attention ---------------------------------------------------------
    attn_pattern: Tuple[LayerKind, ...] = ("global",)
    # trailing layers that don't complete a pattern group (e.g.
    # recurrentgemma's published 26 = 8 x (rglru,rglru,local) + 2 rglru);
    # applied after the scanned groups, so the scan body stays small.
    pattern_tail: Tuple[LayerKind, ...] = ()
    window_size: Optional[int] = None
    qkv_bias: bool = False
    attn_softcap: Optional[float] = None   # gemma2 attention-logit softcap
    logit_softcap: Optional[float] = None  # gemma2 final-logit softcap
    rope_theta: float = 10000.0
    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    # Layout-only transform: store/compute each expert as `s` virtual
    # experts of width d_ff/s.  EXACT for gated MLPs (f-slices are
    # independent through the activation; wo row-blocks sum), and it
    # makes the expert dim divide the model axis (mixtral: 8 experts x
    # split 2 -> 16 on a 16-way mesh), which keeps expert parallelism
    # a clean einsum batch dim through the backward pass.
    moe_virtual_split: int = 1
    # --- SSM (Mamba-1) -----------------------------------------------------
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: Optional[int] = None      # default ceil(d_model/16)
    # --- recurrent (RG-LRU) --------------------------------------------------
    lru_width: Optional[int] = None        # default d_model
    conv1d_width: int = 4
    # --- embeddings / head ---------------------------------------------------
    tie_embeddings: bool = True
    scale_embeddings: bool = False         # gemma: embed * sqrt(d_model)
    # --- modality frontend (vlm/audio): STUB per assignment ------------------
    frontend: Optional[str] = None         # None | "vision" | "audio"
    frontend_tokens: int = 0               # prompt positions fed as embeddings
    # --- numerics -------------------------------------------------------------
    dtype: str = "bfloat16"
    rms_norm_eps: float = 1e-6             # every RMSNorm's epsilon
    # --- training-shape metadata ----------------------------------------------
    max_seq_len: int = 8192

    # ---- derived -----------------------------------------------------------
    def __post_init__(self):
        if self.n_heads and self.n_heads % max(1, self.n_kv_heads):
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if (self.n_layers - len(self.pattern_tail)) % len(self.attn_pattern):
            raise ValueError(
                "n_layers minus tail must be a multiple of the pattern period")
        if self.n_experts and not self.experts_per_token:
            raise ValueError("MoE needs experts_per_token")

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(1, self.n_heads)

    @property
    def resolved_dt_rank(self) -> int:
        return self.ssm_dt_rank or max(1, math.ceil(self.d_model / 16))

    @property
    def resolved_lru_width(self) -> int:
        return self.lru_width or self.d_model

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def pattern_period(self) -> int:
        return len(self.attn_pattern)

    @property
    def n_groups(self) -> int:
        return (self.n_layers - len(self.pattern_tail)) // self.pattern_period

    @property
    def all_kinds(self) -> Tuple[LayerKind, ...]:
        return tuple(self.attn_pattern) + tuple(self.pattern_tail)

    @property
    def is_attention_free(self) -> bool:
        return all(k in ("ssm", "rglru") for k in self.all_kinds)

    @property
    def sub_quadratic(self) -> bool:
        """True if no layer keeps an unbounded full-attention cache —
        the gate for the ``long_500k`` shape (see DESIGN.md §5)."""
        return all(k != "global" for k in self.all_kinds)

    def layer_kind(self, layer_idx: int) -> LayerKind:
        grouped = self.n_groups * self.pattern_period
        if layer_idx >= grouped:
            return self.pattern_tail[layer_idx - grouped]
        return self.attn_pattern[layer_idx % self.pattern_period]

    def decode_cache_len(self, kind: LayerKind, max_len: int) -> int:
        """Cache slots one attention layer allocates for decoding.

        THE sizing rule: ``global`` layers append up to ``max_len``
        positions; ``local`` layers keep a ``window_size`` ring.  Both
        the model's cache construction (init/prefill) and the serving
        telemetry's byte accounting call this, so they cannot drift.
        """
        if kind == "local":
            return min(max_len, self.window_size or max_len)
        return max_len

    # ---- parameter accounting (roofline MODEL_FLOPS) ------------------------
    def param_counts(self) -> dict:
        d, hd = self.d_model, self.resolved_head_dim
        h, k = self.n_heads, self.n_kv_heads
        counts = {"embed": self.vocab_size * d}
        attn = d * h * hd + 2 * d * k * hd + h * hd * d
        if self.qkv_bias:
            attn += (h + 2 * k) * hd
        mlp_dense = d * self.d_ff * (3 if self.mlp_gated else 2)
        per_kind = {}
        for kind in set(self.attn_pattern):
            if kind in ("global", "local"):
                per_kind[kind] = attn + (
                    self.n_experts * mlp_dense + d * self.n_experts
                    if self.n_experts else mlp_dense
                ) + 2 * d
            elif kind == "ssm":
                di, n, r = self.d_inner, self.ssm_state, self.resolved_dt_rank
                per_kind[kind] = (
                    d * 2 * di + di * self.ssm_conv + di * (r + 2 * n)
                    + r * di + di * n + di + di * d + d
                )
            elif kind == "rglru":
                dl = self.resolved_lru_width
                per_kind[kind] = (
                    2 * d * dl + dl * self.conv1d_width + 2 * dl * dl + dl
                    + dl * d + mlp_dense + 2 * d
                )
        counts["blocks"] = sum(
            per_kind[self.layer_kind(i)] for i in range(self.n_layers)
        )
        counts["final_norm"] = d
        counts["lm_head"] = 0 if self.tie_embeddings else d * self.vocab_size
        counts["total"] = sum(counts.values())
        return counts

    def active_param_counts(self) -> int:
        """Active params per token (== total for dense; routed for MoE)."""
        if not self.n_experts:
            return self.param_counts()["total"]
        full = self.param_counts()["total"]
        d = self.d_model
        mlp_dense = d * self.d_ff * (3 if self.mlp_gated else 2)
        inactive = (self.n_experts - self.experts_per_token) * mlp_dense
        return full - self.n_layers * inactive
