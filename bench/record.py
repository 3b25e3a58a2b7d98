"""The program's own span and counter record (``repro.serve.spans``),
which it keeps while a profiler trace is collected: in a ``--trace 1``
run, the measured window."""


def program_record():
    """``(spans, counts)`` as the program recorded them, or ``None``
    where the record is empty or the program keeps none."""
    try:
        from repro.serve import spans
    except ImportError:
        return None
    rec = spans.recorded()
    return rec if rec.spans or rec.counts else None
