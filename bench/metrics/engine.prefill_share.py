"""Share of the window spent in prefill, as the engine times each
admission (prefill, page insertion and first-token sampling)."""


def read(run):
    return 100.0 * run.stats.prefill_s / run.stats.seconds
