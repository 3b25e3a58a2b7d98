"""Least time the chip needs for the paged decode attention of every
decode step in the trace (each slot's live K/V, its query and output),
over the kernel's device time in the trace."""
import re

from bench.counts import paged_attention_need, roofline_share

#: the kernel's operations in the device trace
KERNEL = re.compile(r"paged_decode_attention")


def read(run):
    if run.trace is None:
        return None
    seconds = sum(v for k, v in run.trace["ops"].items() if KERNEL.search(k))
    if seconds <= 0:
        return None
    flops, nbytes = paged_attention_need(run.dims,
                                         run.stats.traced_decode_ctx)
    share, _ = roofline_share(flops, nbytes, seconds,
                              run.peaks["bf16_flops"],
                              run.peaks["hbm_bytes_per_s"])
    return share
