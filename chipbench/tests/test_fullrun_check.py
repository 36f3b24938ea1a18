"""spec06-fullrun-sat's output check at the cell's own row length: SPEC
traces of three rows of 16384 commands, priced row by row with
their segment carries and combined per trace as the service does, pass
``harness.check`` under the cell's limit.  The same rows with their
carries dropped (each row starting cold), and the plain reference
computed in bfloat16 put in the program's place, both come out not
correct.

Run with ``python -m pytest chipbench/tests`` from the repository root
(not part of the repository's own test run).
"""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import harness, reference, spec_gen

SEED = 2**31 + 91
ROW = 16384


@pytest.fixture(scope="module")
def fleet():
    from repro.core import device_sim, fleet
    stacked = fleet.fleet_stacked(device_sim.make_fleet()[::10])
    return stacked, {k: np.asarray(v) for k, v in stacked._asdict().items()}


@pytest.fixture(scope="module")
def traces():
    """Two pool traces of three rows of 16384."""
    return spec_gen.trace_pool(2, 2 * ROW + 1, 3 * ROW, seed=SEED)


def served(traces, stacked, carried: bool) -> list[dict]:
    """Each trace's answer: its rows through the batched estimate (with
    or without their carries), combined as ``EstimationService.step``
    combines a ticket's rows."""
    import jax
    from repro.core.dram import CommandTrace
    from repro.core.estimate_batch import TraceBatch, batched_reports
    from repro.serving.ring import split
    from repro.serving.service import combine_rows
    segs = [split(CommandTrace(*(t[f] for f in spec_gen.FIELDS)), ROW)
            for t in traces]
    rows = [CommandTrace(*(x[lo:hi] for x in g.trace))
            for g in segs for lo, hi in zip(g.bounds[:-1], g.bounds[1:])]
    tb = TraceBatch.from_traces(rows)
    carry = None
    if carried:
        carry = type(segs[0].carry)(*(np.concatenate(f) for f in
                                      zip(*(g.carry for g in segs))))
    rep = jax.tree_util.tree_map(np.asarray, batched_reports(
        tb.trace, tb.weight, stacked, carry))
    whole = combine_rows(rep, [g.rows for g in segs])
    return [{leaf: getattr(whole, leaf)[i] for leaf in reference.LEAVES}
            for i in range(len(segs))]


class _Sample:
    """The slice of a loop's driver ``harness.check`` reads."""

    def __init__(self, traces, answers, params):
        self.samples = [(t, a, False) for t, a in zip(traces, answers)]
        self.system = self
        self._params = params

    def sample(self, rng) -> list:
        return self.samples

    def reference_params(self) -> dict:
        return self._params


@pytest.mark.parametrize("answers,correct", [
    ("carried", True), ("dropped_carry", False), ("bf16_control", False)])
def test_check_at_the_cells_row_length(fleet, traces, answers, correct):
    stacked, params = fleet
    assert [-(-len(t["cmd"]) // ROW) for t in traces] == [3, 3]
    if answers == "bf16_control":
        got = [reference.report(t, params, False, dtype=reference.BFLOAT16)
               for t in traces]
    else:
        got = served(traces, stacked, answers == "carried")
    limits = harness.load_json(harness.BENCH_DIR / "limits"
                               / "spec06-fullrun-sat.json")
    compared = harness.check(_Sample(traces, got, params),
                             harness.Outcome(1.0, len(traces), 0, {}),
                             SEED, limits)
    assert all(c["value"] <= c["limit"]
               for c in compared.values()) == correct, compared
