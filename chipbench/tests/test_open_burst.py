"""The bursty open loop (``loops/open_burst.py``): arrivals fall only in
the on-phases, at the burst rate, and a small run of ``spec06-mean-burst``
on the CPU is correct.

Run with ``python -m pytest chipbench/tests`` from the repository root
(not part of the repository's own test run).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from chipbench import harness
from chipbench.tests import small

SEED = 2**31 + 29
SMALL = dict(rate_per_s=60.0, on_s=0.5, off_s=0.5, pool_traces=16,
             length_min=64, length_max=512, check_sample=8)


def test_arrivals_fall_in_the_on_phases():
    from chipbench.loops.open_burst import Driver
    d = Driver.__new__(Driver)
    d.rate, d.on_s, d.off_s = 768.0, 2.0, 2.0
    d.rng = np.random.default_rng(SEED)
    due = d.arrivals(40.0)
    assert np.all(np.diff(due) >= 0) and due[-1] < 40.0
    assert np.all(due % 4.0 < 2.0)
    per_burst = np.bincount((due // 4.0).astype(int))
    assert len(per_burst) == 10 and np.all(per_burst == int(768 * 2))


def test_small_run_is_correct(monkeypatch, tmp_path_factory):
    small.use_small_fleet(monkeypatch)
    small.use_cache(monkeypatch, tmp_path_factory.getbasetemp() / "cache")
    bench, config, _ = small.cell_inputs("spec06-mean-open")
    traffic = harness.load_json(harness.BENCH_DIR / "traffic"
                                / "spec06-mean-burst.json")
    traffic.update(SMALL)
    args = argparse.Namespace(workload="spec06-mean-burst", seed=SEED,
                              seconds=2.0, trace=0)
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    res = harness.run_cell(args, bench, device, time.perf_counter(),
                           config=config, traffic=traffic)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 60
    assert "latency_p95_ms" in res["metrics"]
