"""Mean time of one decode step as the engine times it (device step,
sampling and the host round trip), in milliseconds."""


def read(run):
    steps = run.stats.decode_ctx
    return 1e3 * run.stats.decode_s / len(steps) if steps else None
