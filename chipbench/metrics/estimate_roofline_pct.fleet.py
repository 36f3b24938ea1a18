"""Roofline share of the fleet surface map: the least time the bytes it
needs take at peak HBM bandwidth, over the device busy time inside the
'dispatch' spans (profiler trace)."""
from chipbench.readings import roofline_pct


def read(run):
    return roofline_pct(run, "dispatch")
