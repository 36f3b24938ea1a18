"""A run of each kind of cell, past the look for a chip, on the CPU at a
small size: sound, it is correct; with the timed path broken underneath
in each way the cell can break, ``correct`` comes out false.

The faults: a dispatch that hands back the previous window's answers
(state left unchanged), half of every trace's commands left out, every
answer altered by one part in a thousand where it is produced, and the
answers handed to the wrong tickets.  Exchange between chips does not
arise: every cell runs on one chip.

Run with ``python -m pytest chipbench/tests`` from the repository root
(not part of the repository's own test run).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests import small

SEED = 2**31 + 17


def stale(result_of):
    last = {}

    def broken(*a, **kw):
        out = result_of(*a, **kw)
        prev = last.get("out", out)
        last["out"] = out
        same = jax.tree_util.tree_structure(prev) == \
            jax.tree_util.tree_structure(out) and all(
                p.shape == o.shape for p, o in zip(jax.tree_util.tree_leaves(prev),
                                                   jax.tree_util.tree_leaves(out)))
        return prev if same else out
    return broken


def altered(out):
    return out._replace(charge_ma_cycles=out.charge_ma_cycles * 1.001,
                        energy_pj=out.energy_pj * 1.001)


def rolled(out):
    return jax.tree_util.tree_map(lambda x: jnp.roll(x, 1, axis=0), out)


def first_half(weight):
    seen = jnp.cumsum(weight, axis=1)
    return weight * (seen <= jnp.sum(weight, axis=1, keepdims=True) / 2)


def plant(monkeypatch, system: str, fault: str) -> None:
    """Break the entry the window drives: the serving engine's dispatch,
    or the fleet surface map."""
    if system == "service":
        from repro.core.estimate_batch import TraceBatch
        from repro.serving.engine import ServingEngine
        orig = ServingEngine.dispatch
        if fault == "stale":
            fn = stale(orig)
        elif fault == "half":
            def fn(self, tb, vendors=None):
                return orig(self, TraceBatch(tb.trace, first_half(tb.weight)),
                            vendors)
        else:
            post = altered if fault == "altered" else rolled

            def fn(self, tb, vendors=None):
                return post(orig(self, tb, vendors))
        monkeypatch.setattr(ServingEngine, "dispatch", fn)
        return
    from repro.core import fleet
    orig = fleet.fleet_surface_energy
    if fault == "stale":
        fn = stale(orig)
    elif fault == "half":
        def fn(modules, trace, weight, *a, **kw):
            return orig(modules, trace, first_half(weight), *a, **kw)
    else:
        post = altered if fault == "altered" else rolled

        def fn(*a, **kw):
            return post(orig(*a, **kw))
    monkeypatch.setattr(fleet, "fleet_surface_energy", fn)


CELLS = [("spec06-mean-sat", "service"), ("spec06-surface-sat", "service"),
         ("spec06-mean-open", "service"), ("fleet50-surface", "fleet")]
FAULTS = ["stale", "half", "altered", "rolled"]


@pytest.fixture(autouse=True)
def small_run(monkeypatch, tmp_path_factory):
    small.use_small_fleet(monkeypatch)
    small.use_cache(monkeypatch, tmp_path_factory.getbasetemp() / "cache")


@pytest.mark.parametrize("workload,system", CELLS)
def test_sound_run_is_correct(workload, system):
    res = small.run(workload, SEED)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload,system", CELLS)
def test_fault_is_not_correct(monkeypatch, workload, system, fault):
    plant(monkeypatch, system, fault)
    res = small.run(workload, SEED)
    assert not res["correct"], res["compared"]
