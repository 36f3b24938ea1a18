"""The per-module power parameters of the paper's DDR3L fleet, mapped
through ``repro.core.fleet.fleet_surface_energy`` (no fit: the
parameters are the simulated modules' own)."""
from __future__ import annotations

import numpy as np

from chipbench import reference


class System:
    def __init__(self, config: dict):
        from repro.core import device_sim, fleet
        self.config = config
        self.modules = device_sim.make_fleet()
        self.stacked = fleet.fleet_stacked(self.modules)
        self.sets = len(self.modules)
        pp = self.stacked._asdict()
        self._params = {k: np.asarray(pp[k]) for k in reference.PARAM_KEYS}

    def surface(self, trace, weight):
        from repro.core import fleet
        return fleet.fleet_surface_energy(self.stacked, trace, weight,
                                          **self.config["surface"])

    def reference_params(self) -> dict:
        return self._params

    def release(self) -> None:
        self.stacked = None
        self.modules = None


def build(config: dict, cache_dir) -> System:
    return System(config)
