"""90th percentile, over every request submitted in the window, of the
time from its job's submission to its first token (a request still
waiting at the deadline counts its wait so far)."""
import numpy as np


def read(run):
    return float(np.percentile(run.stats.ttft_s, 90)) if run.stats.ttft_s \
        else None
