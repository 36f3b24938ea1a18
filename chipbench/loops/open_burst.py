"""Open loop in bursts, through the estimation service: the loop of
``open_poisson`` (one ``submit`` per request when it is due, ``step``
while the ring holds traces, every answer taken with ``result``), with
arrivals only in on-phases.  Each period is ``on_s`` seconds of Poisson
arrivals at ``rate_per_s``, then ``off_s`` seconds of silence, so a burst
builds a backlog that the silence drains.

Traffic keys: those of ``open_poisson`` (``rate_per_s`` is the rate
inside a burst, fixed from the knee ``sweep.py`` finds), ``on_s`` and
``off_s``.  Every burst offers the same gaps, the quantiles of the
exponential law at that rate, in an order shuffled by the seed.
"""
from __future__ import annotations

import numpy as np

from chipbench.loops import open_poisson


class Driver(open_poisson.Driver):
    def __init__(self, system, traffic: dict, seed: int, spans):
        super().__init__(system, traffic, seed, spans)
        self.on_s = float(traffic["on_s"])
        self.off_s = float(traffic["off_s"])

    def arrivals(self, seconds: float) -> np.ndarray:
        period = self.on_s + self.off_s
        n = max(int(self.rate * self.on_s), 1)
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / self.rate
        due = np.concatenate([
            k * period + np.cumsum(self.rng.permutation(gaps))
            for k in range(max(int(np.ceil(seconds / period)), 1))])
        return due[due < seconds]
