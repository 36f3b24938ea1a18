"""The control of the output check at test size: the plain reference
computed in bfloat16 (float32 sums), put in the program's place, reads
above every cell's limit, while the program itself reads below it, on
SPEC-mix traces against the paper fleet's parameters.  On the chip, at
each cell's own size, ``chipbench/control.py`` takes the same readings.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, spec_gen

CELLS = [("spec06-mean-sat", False), ("spec06-surface-sat", True),
         ("spec06-mean-open", False), ("fleet50-surface", True)]


@pytest.fixture(scope="module")
def fleet():
    from repro.core import device_sim, fleet
    stacked = fleet.fleet_stacked(device_sim.make_fleet()[::10])
    return stacked, {k: np.asarray(v) for k, v in stacked._asdict().items()}


@pytest.fixture(scope="module")
def traces():
    return spec_gen.trace_pool(4, 128, 2048, seed=2**31 + 5)


@pytest.mark.parametrize("workload,surface", CELLS)
def test_control_fails_and_program_passes(fleet, traces, workload, surface):
    from repro.core import estimate_batch
    from repro.core.dram import CommandTrace
    limit = harness.load_json(harness.BENCH_DIR / "limits"
                              / f"{workload}.json")["max_gap"]
    stacked, params = fleet
    tb = estimate_batch.bucketed_trace_batch(
        [CommandTrace(*(jnp.asarray(t[f]) for f in spec_gen.FIELDS))
         for t in traces], 8, 2048)
    fn = (estimate_batch.pallas_batched_surface_reports if surface
          else estimate_batch.pallas_batched_reports)
    rep = fn(tb.trace, tb.weight, stacked)
    for i, tr in enumerate(traces):
        ref = reference.report(tr, params, surface)
        ans = {k: np.asarray(getattr(rep, k))[i] for k in reference.LEAVES}
        assert reference.gap(ans, ref) < limit
        ctl = reference.report(tr, params, surface, dtype=reference.BFLOAT16)
        assert reference.gap(ctl, ref) > limit
