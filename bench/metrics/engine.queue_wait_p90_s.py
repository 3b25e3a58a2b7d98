"""90th percentile, over the requests the program admitted in the
traced window, of each one's wait from its ``serve()`` call's entry to
the start of its admission (the program's ``serve.queue_wait`` spans)."""
import numpy as np

from bench.record import program_record


def read(run):
    rec = program_record()
    waits = [s.end_ns - s.start_ns for s in rec.spans
             if s.name == "serve.queue_wait"] if rec else []
    return 1e-9 * float(np.percentile(waits, 90)) if waits else None
