"""The VAMPIRE model, fitted by the characterization campaign, served
through ``repro.serving.EstimationService``.

Set-up fits once per checkout and keeps the fitted model as a schema-v2
blob (``model_api.save_estimator``) under the benchmark's cache
directory, keyed on the fit settings; every later run loads it.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from chipbench import reference, spec_gen


class System:
    """A fitted model and the service configuration it is served with."""

    def __init__(self, model, config: dict):
        self.model = model
        self.config = config
        self.sets = len(model.vendors)
        fm = model.fleet.params._asdict()
        self._params = {k: np.asarray(fm[k]) for k in reference.PARAM_KEYS}

    def service(self, mode: str):
        from repro.serving import EstimationService, ServiceConfig
        return EstimationService(self.model, ServiceConfig(
            mode=mode, **self.config["serve"]))

    @staticmethod
    def request(trace: dict):
        """A trace as a client submits it: host arrays."""
        from repro.core.dram import CommandTrace
        return CommandTrace(*(trace[f] for f in spec_gen.FIELDS))

    @staticmethod
    def answer(row) -> dict:
        return {leaf: np.asarray(getattr(row, leaf))
                for leaf in reference.LEAVES}

    def reference_params(self) -> dict:
        return self._params

    def release(self) -> None:
        self.model = None


def build(config: dict, cache_dir) -> System:
    from repro.core import device_sim, model_api
    fit = dict(config["fit"])
    meta = {"fit": fit, "fleet": config["fleet"]}
    key = hashlib.sha256(json.dumps(meta, sort_keys=True).encode()
                         ).hexdigest()[:16]
    path = cache_dir / f"fit-{key}.npz"
    if path.exists() and (model_api.read_manifest(str(path)) or {}).get("meta") \
            == meta:
        return System(model_api.load_estimator(str(path)), config)
    kind, fitter = fit.pop("kind"), fit.pop("fitter")
    model = model_api.fit(kind, device_sim.make_fleet(), fitter=fitter,
                          **fit)
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".tmp{os.getpid()}.npz")
    model_api.save_estimator(model, str(tmp), meta=meta)
    os.replace(tmp, path)
    return System(model, config)
