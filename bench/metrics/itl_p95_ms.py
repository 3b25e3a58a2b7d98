"""95th percentile of every gap between consecutive tokens of a request
inside the window, in milliseconds."""
import numpy as np


def read(run):
    return 1e3 * float(np.percentile(run.stats.gaps_s, 95)) \
        if run.stats.gaps_s else None
