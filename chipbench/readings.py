"""Arithmetic the metric readers (``metrics/<name>.py``) share."""
from __future__ import annotations

import numpy as np

from chipbench import roofline


def window_spans(run, name: str) -> list[float]:
    """Durations (s) of the host spans ``name`` inside the measured
    window (set-up's spans are left out)."""
    w0, w1 = next((t0, t1) for n, t0, t1 in reversed(run.spans)
                  if n == "window")
    return [t1 - t0 for n, t0, t1 in run.spans
            if n == name and t0 >= w0 and t1 <= w1]


def mean_span_ms(run, name: str):
    d = window_spans(run, name)
    return float(np.mean(d)) * 1e3 if d else None


def idle_pct(run):
    """Share of the traced window in which no operation ran on the
    device."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def roofline_pct(run, span: str):
    """Least time the chip could take for the bytes the window's
    estimation needs, at peak HBM bandwidth, as a share of the device's
    busy time inside the ``span`` host spans."""
    if run.trace is None:
        return None
    busy = run.trace["busy_in_s"].get(span, 0.0)
    needed = roofline.needed_bytes(run.outcome.counters)
    if busy <= 0 or needed <= 0:
        return None
    return 100.0 * needed / run.peaks["hbm_bytes_per_s"] / busy
