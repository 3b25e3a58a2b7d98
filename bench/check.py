"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample
drawn from the seed of the requests the window finished (always with the
longest among them) is run through the plain float32 reference
(:mod:`bench.reference.lm`) over each prompt followed by its served
tokens.  At every served token's position the reference's best logit is
compared with the logit of the token the program served: the widest gap
over all compared tokens, and their mean, are the numbers a cell's
limits file (``limits/<cell>.json``) may hold to a limit.  Greedy
decoding serves the program's own best token, so a sound program's gap
is rounding; a wrong cache, kernel, routing or token is not.  (Where
rounding can flip a sparse layer's choice of experts, the widest gap of
a sound program can be as wide as a wrong one's, and the mean is the
number that separates them.)

The control (:func:`control_gaps`) puts the same reference, computed in
float8, in the program's place: at each position the token it ranks
first is read off the float32 reference the same way.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from bench.weights import Dims

__all__ = ["pick", "served_gaps", "control_gaps", "malformed", "numbers",
           "correct"]

Request = Tuple[np.ndarray, np.ndarray]    # (prompt, served tokens)


def pick(finished: Sequence[Request], seed: int, check_tokens: int
         ) -> List[int]:
    """Indices of the requests to compare: the longest (prompt plus
    served), then others in an order drawn from the seed, until
    ``check_tokens`` served tokens are covered."""
    if not finished:
        return []
    total = [len(p) + len(s) for p, s in finished]
    first = int(np.argmax(total))
    rest = [i for i in np.random.default_rng([int(seed), 7]).permutation(
        len(finished)) if i != first]
    out, n = [first], len(finished[first][1])
    for i in rest:
        if n >= check_tokens:
            break
        out.append(int(i))
        n += len(finished[i][1])
    return out


def malformed(finished: Sequence[Request], new_tokens: int, vocab: int
              ) -> int:
    """Requests that did not get exactly ``new_tokens`` in-vocabulary
    tokens."""
    return sum(1 for _, s in finished
               if len(s) != new_tokens or (len(s) and (
                   int(np.min(s)) < 0 or int(np.max(s)) >= vocab)))


def _inputs(reqs: Sequence[Request]):
    from bench.reference.lm import served_rows
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in reqs]
    rows = [served_rows(len(p), len(s)) for p, s in reqs]
    served = np.concatenate([s for _, s in reqs]).astype(np.int32)
    return seqs, rows, served


def _gaps(ref, chosen) -> np.ndarray:
    import jax.numpy as jnp
    chosen = jnp.asarray(chosen)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)


def served_gaps(dm: Dims, seed: int, reqs: Sequence[Request], length: int,
                margins: bool = False):
    """The reference's best logit less the served token's, per token
    (and, with ``margins``, the reference's routing margin there)."""
    from bench.reference.lm import final_logits
    seqs, rows, served = _inputs(reqs)
    ref, margin = final_logits(dm, seed, seqs, rows, length, margins=True)
    gaps = _gaps(ref, served)
    return (gaps, np.asarray(margin)) if margins else gaps


def control_gaps(dm: Dims, seed: int, reqs: Sequence[Request], length: int
                 ) -> np.ndarray:
    """The same gaps for the tokens a float8 reference ranks first."""
    import jax.numpy as jnp
    from bench.reference.lm import final_logits
    seqs, rows, _ = _inputs(reqs)
    low = final_logits(dm, seed, seqs, rows, length, quant="fp8")
    chosen = np.asarray(jnp.argmax(low, axis=-1))
    del low
    ref = final_logits(dm, seed, seqs, rows, length)
    return _gaps(ref, chosen)


def numbers(gaps: np.ndarray) -> dict:
    """The numbers a limit may hold, from the per-token gaps."""
    return {"max_logit_gap": float(np.max(gaps)),
            "mean_logit_gap": float(np.mean(gaps))}


def correct(numbers: dict, limits: dict, malformed_requests: int,
            compared: int) -> bool:
    """A run's verdict: limits to hold, no malformed request, at least one
    request compared, and every number within its limit."""
    return (bool(limits) and malformed_requests == 0 and compared >= 1
            and all(numbers[n] <= limits[n]["limit"] for n in limits))
