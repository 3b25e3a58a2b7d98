"""Mean share of the engine's slots that are live in a decode step."""


def read(run):
    steps = run.stats.decode_ctx
    if not steps:
        return None
    return 100.0 * sum(len(c) for c in steps) / (len(steps) * run.stats.slots)
