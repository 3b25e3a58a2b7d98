"""Host time of the page table per decode step, in milliseconds: its
``page_table.grow``, ``page_table.release`` and ``page_table.insert``
spans over the program's ``serve.decode_steps`` counter.  The spans
time the host, which dispatches the page table's programs; their device
time is in the device trace."""
from bench.record import program_record

SPANS = ("page_table.grow", "page_table.release", "page_table.insert")


def read(run):
    rec = program_record()
    steps = rec.counts.get("serve.decode_steps", 0) if rec else 0
    if not steps:
        return None
    ns = sum(s.end_ns - s.start_ns for s in rec.spans if s.name in SPANS)
    return 1e-6 * ns / steps
