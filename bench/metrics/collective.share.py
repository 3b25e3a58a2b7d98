"""Device time of the collectives over busy time, both averaged over the
cell's chips.

A trace names an operation by its HLO instruction: the collectives
XLA inserts are ``all-reduce``, ``all-gather``, ``reduce-scatter``,
``all-to-all`` and ``collective-permute``; a sum the program writes
(``jax.lax.psum``) is an all-reduce named ``psum``, a ``ppermute`` a
collective-permute."""
import re

COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|psum|ppermute)\b")


def read(run):
    t = run.trace
    if t is None:
        return None
    seconds = sum(v for k, v in t["ops"].items()
                  if COLLECTIVE.search(k.split(" = ", 1)[0]))
    return 100.0 * seconds / run.chips / t["busy_s"]
