"""Small stand-ins of the benchmark's configurations and traffic, sized
for a test run on the CPU (Pallas in interpret mode)."""
from __future__ import annotations

import argparse
import pathlib

from chipbench import harness

#: the 9-module fleet the program's own tests fit
SMALL_FLEET = [(v, i, 2015) for v in range(3) for i in range(3)]
TRAFFIC = {
    "closed_windows": dict(window_traces=8, pool_traces=16, length_min=64,
                           length_max=1024, schedule_epochs=1,
                           check_sample=8),
    "open_poisson": dict(rate_per_s=40.0, pool_traces=16, length_min=64,
                         length_max=512, check_sample=8),
    "closed_dispatch": dict(segment_length=256, rows=24, batches=2,
                            check_sample=4, keep=2),
}


def use_small_fleet(monkeypatch) -> None:
    from repro.core import device_sim
    from repro.core import params as P
    make = device_sim.make_fleet
    specs = [P.ModuleSpec(*s) for s in SMALL_FLEET]
    monkeypatch.setattr(device_sim, "make_fleet",
                        lambda s=None: make(specs if s is None else s))


def use_cache(monkeypatch, path: pathlib.Path) -> None:
    monkeypatch.setattr(harness, "CACHE_DIR", path)
    monkeypatch.setattr(harness, "JAX_CACHE", path / "jax")
    monkeypatch.setattr(harness, "PROFILE_DIR", path / "profile")


def cell_inputs(workload: str):
    """(bench, config, traffic) of a cell, cut to test size."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.cell_of(bench, workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(harness.ROOT / entry["file"])
    if "fit" in config:
        config["fit"].update(probe_modules=2, probe_reps=64, n_rows=8)
    traffic = harness.load_json(harness.BENCH_DIR / "traffic"
                                / f"{cell['traffic']}.json")
    traffic.update(TRAFFIC[traffic["loop"]])
    return bench, config, traffic


def run(workload: str, seed: int, seconds: float = 1.0) -> dict:
    """One run of a cell past the look for a chip."""
    import time
    bench, config, traffic = cell_inputs(workload)
    args = argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=0)
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return harness.run_cell(args, bench, device, time.perf_counter(),
                            config=config, traffic=traffic)
