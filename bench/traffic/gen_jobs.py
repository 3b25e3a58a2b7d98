"""Offline batch jobs: the traffic ``ServeEngine.serve`` takes today.

A job is one ``serve()`` call over ``requests_per_slot x slots`` requests,
all submitted at once, greedy, each asking for ``new_tokens`` tokens.
Jobs run back to back until the window closes.

Prompt lengths follow an exponential law with the published mean
``prompt_mean``: the law a positive length takes when its mean is all a
source states, so no spread is set by hand.  They are clipped to
``[prompt_min, prompt_max]`` (``null``: the engine's ``max_len`` less
``new_tokens``).  Every job holds the same multiset of lengths, the law's
quantiles at ``(i + 0.5) / n``, so every seed does the same work; the
seed only orders them within each job and draws the token ids (uniform
over the vocabulary).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List

import numpy as np

__all__ = ["Job", "lengths", "jobs"]


@dataclasses.dataclass(frozen=True)
class Job:
    index: int
    prompts: List[np.ndarray]
    new_tokens: int


def _n_requests(mix: dict, geometry: dict) -> int:
    return int(mix["requests_per_slot"]) * int(geometry["slots"])


def lengths(mix: dict, geometry: dict) -> List[int]:
    """The prompt lengths of one job, in ascending order."""
    n = _n_requests(mix, geometry)
    top = mix.get("prompt_max")
    if top is None:
        top = int(geometry["max_len"]) - int(mix["new_tokens"])
    mean = float(mix["prompt_mean"])
    out = [int(np.clip(round(-mean * math.log(1.0 - (i + 0.5) / n)),
                       mix["prompt_min"], top)) for i in range(n)]
    return sorted(out)


def jobs(mix: dict, geometry: dict, vocab: int, seed: int) -> Iterator[Job]:
    """Endless jobs for ``seed``; job ``j`` depends on ``(seed, j)`` only."""
    base = lengths(mix, geometry)
    j = 0
    while True:
        rng = np.random.default_rng([int(seed), j])
        order = rng.permutation(len(base))
        prompts = [rng.integers(0, vocab, (base[i],)).astype(np.int32)
                   for i in order]
        yield Job(j, prompts, int(mix["new_tokens"]))
        j += 1
