"""Least time the chips need for the expert layers of every traced decode
step, over the grouped matmul's device time summed over the cell's
chips.

The work of one step, per layer: every expert's weights streamed once
(the chips together hold each expert once, in shares), and two
operations per weight of an expert for each row routed to it
(``top_k`` rows for each live slot).  Prefill's grouped matmuls are in
the device time and not in the work, so prefill lowers the share."""
import re

from bench.counts import roofline_share

#: the grouped matmul's operations in the device trace
KERNEL = re.compile(r"^grouped_matmul\b")


def expert_need(dm, steps, itemsize=2):
    """(operations, bytes) of the expert layers of the decode ``steps``
    (each step: the live slots' context lengths), over every layer."""
    weights = 3.0 * dm.d * dm.ff                # gate, up, down
    flops = nbytes = 0.0
    for ctx in steps:
        flops += 2.0 * weights * dm.top_k * len(ctx)
        nbytes += itemsize * weights * dm.experts
    return flops * dm.layers, nbytes * dm.layers


def read(run):
    if run.trace is None or not run.dims.experts:
        return None
    seconds = sum(v for k, v in run.trace["ops"].items() if KERNEL.search(k))
    if seconds <= 0:
        return None
    flops, nbytes = expert_need(run.dims, run.stats.traced_decode_ctx)
    share, _ = roofline_share(flops, nbytes, seconds,
                              run.peaks["bf16_flops"],
                              run.peaks["hbm_bytes_per_s"])
    return share
