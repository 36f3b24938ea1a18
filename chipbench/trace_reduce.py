"""From the profiler's trace to the numbers the benchmark reports.

:func:`load` reads an ``.xplane.pb`` into plain lists: the device
operations of every chip (``[name, start_ns, duration_ns]``) and the host
spans the benchmark wrote around its calls into the program
(``jax.profiler.TraceAnnotation``, same clock).  :func:`reduce` turns
those lists into device busy and idle time over the measured window,
device busy time inside each kind of host span, the device operations
that took most time, and the longest idle gaps, each named by the host
span it fell in.  The reduction is plain arithmetic on the lists, so it
is tested on a recorded excerpt (``tests/test_trace_reduce.py``).
"""
from __future__ import annotations

import bisect
import collections
import re

#: planes of the chips; the line of each that holds one event per
#: executed XLA operation (Pallas kernels included, as custom calls), and
#: the line of the programs (jitted functions) they ran in
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the host spans the benchmark writes; ``window`` bounds the measurement
SPAN_NAMES = ("window", "admit", "step", "result", "dispatch", "idle_wait")
TOP = 10


def op_name(hlo: str, program: str) -> str:
    """A short, stable name for a device operation: its program, the
    HLO instruction and its operand types, without layouts.  The trace
    names an operation by its whole HLO text; a Pallas kernel shows as a
    ``custom-call``."""
    text = re.sub(r"\{[^{}]*\}", "", hlo.split(", kind=")[0])
    return f"{program} {text}"[:200]


def load(path: str) -> dict:
    """``{"device": [[[name, start_ns, dur_ns], ...] per chip],
    "host": [[name, start_ns, dur_ns], ...]}`` from one xplane file;
    device operations are named by :func:`op_name`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX) and \
                plane.name[len(DEVICE_PLANE_PREFIX):].isdigit():
            lines = {line.name: list(line.events) for line in plane.lines}
            programs = sorted((float(e.start_ns), e.name.split("(")[0])
                              for e in lines.get(MODULES_LINE, ()))
            starts = [p[0] for p in programs]
            ops = []
            for e in lines.get(OPS_LINE, ()):
                k = bisect.bisect_right(starts, float(e.start_ns)) - 1
                ops.append([op_name(e.name, programs[k][1] if k >= 0
                                    else "?"),
                            float(e.start_ns), float(e.duration_ns)])
            device.append(ops)
        elif plane.name.startswith("/host:"):
            host += [[e.name, float(e.start_ns), float(e.duration_ns)]
                     for line in plane.lines for e in line.events
                     if e.name in SPAN_NAMES]
    return {"device": device, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a, b) -> float:
    """Total length of the intersection of two disjoint sorted unions."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(trace: dict) -> dict:
    """Busy, idle, per-span busy, top operations and idle gaps of the
    ``window`` span of a :func:`load` result.  Times in seconds; device
    figures are averaged over the chips."""
    windows = [(s, s + d) for n, s, d in trace["host"] if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    window = windows
    w0, w1 = window[0]
    spans = collections.defaultdict(list)
    for n, s, d in trace["host"]:
        if n != "window" and s + d > w0 and s < w1:
            spans[n].append((max(s, w0), min(s + d, w1)))
    spans = {n: union(iv) for n, iv in spans.items()}
    chips = trace["device"]
    if not chips:
        raise ValueError("the trace holds no device plane")
    busy = 0.0
    busy_in = collections.Counter()
    op_time = collections.Counter()
    gaps = []
    for ops in chips:
        merged = union((max(s, w0), min(s + d, w1)) for _, s, d in ops
                       if s + d > w0 and s < w1)
        busy += overlap(merged, window)
        for n, iv in spans.items():
            busy_in[n] += overlap(merged, iv)
        for name, s, d in ops:
            if s + d > w0 and s < w1:
                op_time[name] += min(s + d, w1) - max(s, w0)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))
    n_chips = len(chips)
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n_chips * 1e-9,
        "busy_in_s": {n: v / n_chips * 1e-9 for n, v in busy_in.items()},
        "device_ops": [[n, t / n_chips * 1e-9]
                       for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[_host_activity((s, s + t), spans), t * 1e-9]
                      for t, s in gaps[:TOP]],
    }


def _host_activity(gap, spans) -> str:
    """The host span kind that covers most of an idle gap."""
    best, name = 0.0, "none"
    for n, iv in spans.items():
        i = max(bisect.bisect_right(iv, (gap[0],)) - 1, 0)
        o = 0.0
        while i < len(iv) and iv[i][0] < gap[1]:
            o += max(0.0, min(iv[i][1], gap[1]) - max(iv[i][0], gap[0]))
            i += 1
        if o > best:
            best, name = o, n
    return name
