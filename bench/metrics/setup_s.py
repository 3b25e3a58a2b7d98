"""Seconds from process start to the window's first request: backend,
weights, engine, loading or compiling programs, warm-up."""


def read(run):
    return run.setup_s
