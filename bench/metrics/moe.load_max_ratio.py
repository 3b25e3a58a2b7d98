"""The busiest expert's rows against an even split: the program's
``moe.rows_max`` (the busiest expert's rows in each layer and decode
step, summed) times the experts, over ``moe.rows`` (every routed row).
1.0 is an even load."""
from bench.record import program_record


def read(run):
    rec = program_record()
    if rec is None or not run.dims.experts:
        return None
    rows = rec.counts.get("moe.rows", 0)
    if not rows:
        return None
    return rec.counts.get("moe.rows_max", 0) * run.dims.experts / rows
