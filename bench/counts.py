"""Operations and bytes that the work needs, from shapes alone.

These are the yardstick's counts, kept with the benchmark so that a
change to the program cannot move them.  They count what the algorithm
needs, not what an implementation happens to do: attention reads the
live context of each slot and nothing past it, and a model step costs
two operations per active weight per token.
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple

from bench.weights import Dims

__all__ = ["paged_attention_need", "layer_weights", "model_flops",
           "roofline_share"]


def paged_attention_need(dm: Dims, steps: Iterable[Sequence[int]],
                         itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of the decode attention calls of ``steps``
    (each step: the live slots' context lengths), over every layer.

    Per layer, step and live slot of context ``c``: K and V rows
    ``2 * c * kv_heads * head_dim`` elements read, the query read and the
    output written (``heads * head_dim`` each), and ``4 * c * heads *
    head_dim`` operations (scores and the weighted sum)."""
    hq = dm.heads * dm.head_dim
    kv = dm.kv_heads * dm.head_dim
    flops = nbytes = 0.0
    for ctx in steps:
        c = float(sum(ctx))
        flops += 4.0 * c * hq
        nbytes += itemsize * (2.0 * c * kv + 2.0 * len(ctx) * hq)
    return flops * dm.layers, nbytes * dm.layers


def layer_weights(dm: Dims) -> int:
    """Weights one token multiplies through in one layer (active experts
    only, router included)."""
    hq = dm.heads * dm.head_dim
    kv = dm.kv_heads * dm.head_dim
    attn = 2 * dm.d * hq + 2 * dm.d * kv
    mlp = 3 * dm.d * dm.ff
    if dm.experts:
        return attn + dm.top_k * mlp + dm.d * dm.experts
    return attn + mlp


def model_flops(dm: Dims, prompts: Iterable[int],
                steps: Iterable[Sequence[int]]) -> float:
    """Operations of prefilling ``prompts`` (lengths; logits for the last
    position only) and of the decode ``steps`` (live contexts each).
    Two per weight per token, plus causal attention over what each token
    sees, plus the output head for every token whose logits are used."""
    hq = dm.heads * dm.head_dim
    w = 2.0 * dm.layers * layer_weights(dm)
    head = 2.0 * dm.d * dm.vocab
    att = 4.0 * hq * dm.layers        # per (query, key) pair
    total = 0.0
    for n in prompts:
        total += n * w + head + att * n * (n + 1) / 2.0
    for ctx in steps:
        total += len(ctx) * (w + head) + att * float(sum(ctx))
    return total


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes_per_s: float
                   ) -> Tuple[float, str]:
    """(share in %, bound) of the least time the chip needs for the work
    over the time it took.  Over 100% means the counts or the time are
    wrong, and raises rather than clip."""
    if seconds <= 0:
        raise ValueError(f"no time measured ({seconds} s)")
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes_per_s
    least = max(t_flops, t_bytes)
    share = 100.0 * least / seconds
    if share > 100.0:
        raise ValueError(
            f"roofline share {share:.3f}% > 100%: {flops:.4g} operations "
            f"and {nbytes:.4g} bytes cannot take {seconds:.6g} s")
    return share, "compute" if t_flops >= t_bytes else "memory"
