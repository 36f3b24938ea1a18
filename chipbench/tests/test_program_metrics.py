"""The metrics that read the program's own spans (``program_spans.py``):
from a small run of each kind of cell on the CPU, every one of them
listed for the cell reads a number, and none reads anything when the
program's recorder dropped records inside the window.

Run with ``python -m pytest chipbench/tests`` from the repository root.
"""
from __future__ import annotations

import pytest

from chipbench import harness
from chipbench.tests import small

SEED = 2**31 + 23
CELLS = ["spec06-mean-sat", "spec06-mean-open", "fleet50-surface"]


@pytest.fixture(autouse=True)
def small_run(monkeypatch, tmp_path_factory):
    small.use_small_fleet(monkeypatch)
    small.use_cache(monkeypatch, tmp_path_factory.getbasetemp() / "cache")


def program_metrics(bench: dict, workload: str) -> list[str]:
    return [m["name"] for m in harness.metrics_of(bench, "per_layer",
                                                  workload)
            if m["source"] in ("program_span", "program_counter")]


def measured_run(workload: str) -> tuple[dict, harness.Run]:
    bench, config, traffic = small.cell_inputs(workload)
    _, _, driver, spans = harness.prepare(workload, SEED, bench=bench,
                                          config=config, traffic=traffic)
    with spans("window"):
        outcome = driver.run(1.0)
    driver.release()
    return bench, harness.Run(0.0, outcome, spans.records, None, {})


@pytest.mark.parametrize("workload", CELLS)
def test_program_metrics_read_numbers(workload):
    bench, run = measured_run(workload)
    names = program_metrics(bench, workload)
    assert names
    values = {n: harness.read_metric(n, run) for n in names}
    assert all(isinstance(v, float) and v >= 0 for v in values.values()), \
        values
    if "command_fill_pct" in values:
        assert 0 < values["command_fill_pct"] <= 100


@pytest.mark.parametrize("workload", CELLS)
def test_program_metrics_read_nothing_after_a_drop(monkeypatch, workload):
    from repro.runtime.spans import RECORDER
    bench, run = measured_run(workload)
    w0 = next(t0 for n, t0, _ in run.spans if n == "window")
    monkeypatch.setattr(RECORDER, "dropped_until", w0)
    for name in program_metrics(bench, workload):
        assert harness.read_metric(name, run) is None, name
