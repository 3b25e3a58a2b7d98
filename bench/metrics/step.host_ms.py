"""Mean host time of one decode loop iteration, in milliseconds: each
``serve.step`` span less its ``serve.decode``, ``serve.token_pull`` and
``serve.admit`` children (what is left is the page table's growth and
release, the loop's own Python and the telemetry hook)."""
import bisect

from bench.record import program_record

TIMED = ("serve.decode", "serve.token_pull", "serve.admit")


def read(run):
    rec = program_record()
    if rec is None:
        return None
    steps = sorted((s.start_ns, s.end_ns) for s in rec.spans
                   if s.name == "serve.step")
    if not steps:
        return None
    starts = [a for a, _ in steps]
    own = [b - a for a, b in steps]
    for s in rec.spans:
        if s.parent == "serve.step" and s.name in TIMED:
            own[bisect.bisect_right(starts, s.start_ns) - 1] -= (
                s.end_ns - s.start_ns)
    return 1e-6 * sum(own) / len(own)
