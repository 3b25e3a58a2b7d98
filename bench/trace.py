"""From a profiler trace to device time, idle time and what the host did.

:func:`read_xplane` turns the profiler's ``.xplane.pb`` into plain lists:
the device operations of each chip (name, start, end, in ns) and the
benchmark's own host spans (names starting ``bench.``).  :func:`reduce`
works on those lists only, so a small recorded trace can test it.

Within the window (the host span ``bench.window``):

* busy: the union of a chip's operation intervals, averaged over chips;
* operations: seconds per operation name, summed over chips, each
  operation's own time (a loop's time less the operations inside it);
* idle gaps: each stretch with no operation on a chip is charged to the
  innermost benchmark host span that covers its middle (what the host
  was doing), or to ``host: serve loop`` where none does, averaged over
  chips.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

__all__ = ["read_xplane", "reduce", "DEVICE_PLANE"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
OUTER = ("bench.window", "bench.job")


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"want one .xplane.pb under {directory}, found "
                           f"{len(paths)}")
    return paths[0]


def short_name(hlo: str) -> str:
    """``%copy.71 = bf16[1,2050]{4,3,...} copy(...)`` -> ``copy.71 =
    bf16[1,2050]``: the instruction's name and result type."""
    return hlo.split("{", 1)[0].lstrip("%").strip()


def read_xplane(path: str) -> dict:
    """``{"device": {plane: [[name, start_ns, end_ns], ...]},
    "host": [[name, start_ns, end_ns], ...]}``.  A device event's name is
    its instruction's (:func:`short_name`); events of one plane nest (a
    loop holds the operations of its body)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            device[plane.name] = [
                [short_name(ev.name), ev.start_ns,
                 ev.start_ns + ev.duration_ns]
                for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns])
    return {"device": device, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _self_times(events) -> Dict[str, float]:
    """Own time per name of nested events ``(start, end, name)``: each
    event's length less that of the events directly inside it."""
    own: Dict[str, float] = collections.defaultdict(float)
    stack: list = []            # [end, name, time of children]

    def close(item):
        end, name, start, kids = item
        own[name] += max(0.0, end - start - kids)

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([b, name, a, 0.0])
    while stack:
        close(stack.pop())
    return own


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and window seconds, seconds per operation and idle seconds per
    host activity inside the ``bench.window`` span."""
    windows = [h for h in trace["host"] if h[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"want one {WINDOW} span, found {len(windows)}")
    w0, w1 = float(windows[0][1]), float(windows[0][2])
    # host spans, longest first, so that an inner span overwrites the
    # label its outer span gave
    spans = sorted(((float(a), float(b), n) for n, a, b in trace["host"]
                    if n not in OUTER), key=lambda s: s[0] - s[1])
    labels = ["host: serve loop"] + sorted({n for _, _, n in spans})
    code = {n: i for i, n in enumerate(labels)}
    planes = sorted(trace["device"])
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    ops: Dict[str, float] = collections.defaultdict(float)
    idle = np.zeros(len(labels))
    busy = 0.0
    for p in planes:
        iv = []
        for name, a, b in trace["device"][p]:
            a, b = max(float(a), w0), min(float(b), w1)
            if b > a:
                iv.append((a, b, name))
        for name, t in _self_times(iv).items():
            ops[name] += t * 1e-9
        iv = [(a, b) for a, b, _ in iv]
        merged = _union(iv)
        busy += sum(b - a for a, b in merged)
        edges = np.asarray([w0] + [x for ab in merged for x in ab] + [w1])
        g0, g1 = edges[0::2], edges[1::2]
        keep = g1 > g0
        g0, g1 = g0[keep], g1[keep]
        mid = 0.5 * (g0 + g1)
        order = np.argsort(mid)
        mid_sorted = mid[order]
        who = np.zeros(len(mid), np.int64)
        for s0, s1, n in spans:
            lo = np.searchsorted(mid_sorted, s0, "left")
            hi = np.searchsorted(mid_sorted, s1, "right")
            who[order[lo:hi]] = code[n]
        np.add.at(idle, who, (g1 - g0) * 1e-9 / len(planes))
    window_s = (w1 - w0) * 1e-9
    busy_s = busy * 1e-9 / len(planes)
    if busy_s <= 0:
        raise RuntimeError("no device operation ran inside the window")
    idle_d = {labels[i]: float(v) for i, v in enumerate(idle) if v > 0}
    return {"busy_s": busy_s, "window_s": window_s, "ops": dict(ops),
            "device_ops": _rank(ops, top), "idle_gaps": _rank(idle_d, top)}


def _rank(d: Dict[str, float], top: int) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
