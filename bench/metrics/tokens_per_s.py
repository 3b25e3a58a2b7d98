"""Generated tokens whose host stamp lies inside the window, over the
window's length."""


def read(run):
    return run.stats.tokens / run.stats.seconds
