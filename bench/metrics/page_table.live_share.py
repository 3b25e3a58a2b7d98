"""Share of the KV page pool that live slots hold, over the decode steps
of the traced window (the program's ``page_table.pages_live`` over its
``page_table.pages_pool`` counter)."""
from bench.record import program_record


def read(run):
    rec = program_record()
    pool = rec.counts.get("page_table.pages_pool", 0) if rec else 0
    if not pool:
        return None
    return 100.0 * rec.counts.get("page_table.pages_live", 0) / pool
