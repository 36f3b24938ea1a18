"""Roofline share of the served estimation: the least time the bytes
it needs take at peak HBM bandwidth, over the device busy time inside
the 'step' spans (profiler trace)."""
from chipbench.readings import roofline_pct


def read(run):
    return roofline_pct(run, "step")
