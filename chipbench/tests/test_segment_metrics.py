"""Segmented serving in the benchmark: the per-layer metrics of whole
traces cut into ring rows (``trace.segment``, ``service.combine``), read
from a small CPU run of ``spec06-fullrun-sat``'s loop whose ring is cut
small, so that every pool trace needs several rows.

Run with ``python -m pytest chipbench/tests`` from the repository root.
"""
from __future__ import annotations

import pytest

from chipbench import harness
from chipbench.tests import small

SEED = 2**31 + 57
METRICS = ("segment_ms_per_window", "combine_ms_per_window",
           "segment_rows_per_window")


@pytest.fixture
def small_segments(monkeypatch, tmp_path_factory):
    """A service whose ring holds 16 rows of 512 commands: the small
    pool's traces of 600-3000 commands need 2 to 6 rows each."""
    from chipbench.systems import vampire_service
    from repro.serving import EstimationService, RingConfig, ServiceConfig
    small.use_small_fleet(monkeypatch)
    small.use_cache(monkeypatch, tmp_path_factory.getbasetemp() / "cache")

    def service(self, mode):
        return EstimationService(self.model, ServiceConfig(
            mode=mode, ring=RingConfig(length_buckets=(256, 512),
                                       count_buckets=(8, 16)),
            **self.config["serve"]))
    monkeypatch.setattr(vampire_service.System, "service", service)


def test_segment_metrics_read_numbers_and_answers_are_whole(small_segments):
    workload = "spec06-fullrun-sat"
    bench, config, traffic = small.cell_inputs(workload)
    traffic.update(window_traces=4, pool_traces=8, length_min=600,
                   length_max=3000, schedule_epochs=1, check_sample=4)
    _, _, driver, spans = harness.prepare(workload, SEED, bench=bench,
                                          config=config, traffic=traffic)
    with spans("window"):
        outcome = driver.run(1.0)
    run = harness.Run(0.0, outcome, spans.records, None, {})
    names = [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                   workload)]
    assert set(METRICS) <= set(names)
    values = {n: harness.read_metric(n, run) for n in METRICS}
    assert all(isinstance(v, float) and v > 0 for v in values.values()), \
        values
    # 4 traces a window, each cut into 2-6 rows
    assert 8 <= values["segment_rows_per_window"] <= 24
    driver.release()
    checks = harness.check(driver, outcome, SEED, {"max_gap": 1e-4})
    assert checks["failed"]["value"] == 0
    assert checks["max_gap"]["value"] <= 1e-4

