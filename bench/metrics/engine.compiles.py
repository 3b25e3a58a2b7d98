"""Programs the backend compiled inside ``serve()`` calls of the traced
window (the program's ``serve.compiles`` counter)."""
from bench.record import program_record


def read(run):
    rec = program_record()
    return rec.counts.get("serve.compiles", 0) if rec else None
