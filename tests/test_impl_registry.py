"""The impl registry (``model_api.resolve_impl``) and its golden parity
suite: ``impl='pallas'`` (interpret mode on CPU) == ``impl='vectorized'``
== the per-command ``impl='reference'`` oracle, leaf for leaf, for all
three estimator kinds x all three modes, over ragged NOP/dt=0-padded
batches and vendor subsets — and pad rows must contribute exactly zero
energy.  Also covers the call-time platform detection in
``kernels/common`` and the campaign engine's fused measurement path."""
import jax
import numpy as np
import pytest

from repro.core import dram, idd_loops, model_api, traces
from repro.core.baselines_power import DRAMPowerModel, MicronModel
from repro.core.dram import (ACT, NOP, PDE, PDE_SLOW, PDX, PRE, PREA, RD,
                             SRE, SRX, WR, TIMING)
from repro.kernels import common as kcommon

_T = TIMING

MODE_KW = {"mean": {}, "range": {}, "surface": {},
           "distribution": dict(ones_frac=0.35, toggle_frac=0.15)}


def _pde_trace():
    """PDE/PDX around RD/WR activity (background-state edge cases)."""
    return dram.make_trace(
        [ACT, RD, RD, PREA, PDE, PDX, ACT, WR, PRE],
        [0, 0, 0, 0, 0, 0, 2, 2, 2],
        [5, 5, 5, 0, 0, 0, 9, 9, 0],
        [0, 0, 1, 0, 0, 0, 0, 3, 0],
        None,
        [_T.tRCD, _T.tCCD, _T.tCCD, _T.tRP, 200, _T.tCKE,
         _T.tRCD, _T.tBURST, _T.tRP])


def _lowpower_trace():
    """Every background state in one trace: fast PDN, slow PDN (DLL off),
    active PDN (bank open across the window), and self-refresh."""
    return dram.make_trace(
        [ACT, RD, PREA, PDE, NOP, PDX,
         PDE_SLOW, NOP, PDX,
         ACT, PDE, NOP, PDX, PREA,
         SRE, NOP, SRX, ACT, WR, PRE],
        [0, 0, 0, 0, 0, 0,
         0, 0, 0,
         3, 3, 3, 3, 3,
         0, 0, 0, 1, 1, 1],
        [5, 5, 0, 0, 0, 0,
         0, 0, 0,
         9, 9, 9, 9, 0,
         0, 0, 0, 2, 2, 0],
        [0, 1, 0, 0, 0, 0,
         0, 0, 0,
         0, 0, 0, 0, 0,
         0, 0, 0, 0, 3, 0],
        None,
        [_T.tRCD, _T.tBURST, _T.tRP, _T.tCKE, 120, _T.tXP,
         _T.tCKE, 300, _T.tXPDLL,
         _T.tRCD, _T.tCKE, 180, _T.tXP, _T.tRP,
         _T.tCKE, 900, _T.tXS, _T.tRCD, _T.tBURST, _T.tRP])


@pytest.fixture(scope="module")
def ragged():
    trs = [traces.app_trace(traces.SPEC_APPS[i], n_requests=n)
           for i, n in ((0, 90), (4, 150))]
    trs.append(idd_loops.validation_sweep(24))
    trs.append(_pde_trace())
    trs.append(_lowpower_trace())
    return trs


@pytest.fixture(scope="module")
def estimators(quick_vampire):
    return (quick_vampire, MicronModel.from_vampire(quick_vampire),
            DRAMPowerModel.from_vampire(quick_vampire))


def _reports(rep, mode):
    return rep if mode == "range" else (rep,)


# ---------------------------------------------------------------------------
# Golden parity: all estimators x all modes x all impls
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ("mean", "range", "distribution", "surface"))
def test_golden_parity_every_estimator_and_impl(estimators, ragged, mode):
    kw = MODE_KW[mode]
    shape = ((len(ragged), 3, dram.N_BANKS, dram.N_ROW_BANDS)
             if mode == "surface" else (len(ragged), 3))
    for est in estimators:
        base = est.estimate(ragged, mode=mode, **kw)
        assert _reports(base, mode)[0].energy_pj.shape == shape
        for impl in ("pallas", "reference"):
            other = est.estimate(ragged, mode=mode, impl=impl, **kw)
            for b, o in zip(_reports(base, mode), _reports(other, mode)):
                for name, lb, lo in zip(b._fields, b, o):
                    np.testing.assert_allclose(
                        np.asarray(lo), np.asarray(lb), rtol=1e-5,
                        err_msg=f"{est.kind} mode={mode} impl={impl} "
                                f"leaf {name}")


@pytest.mark.parametrize("mode", ("mean", "range", "distribution", "surface"))
def test_golden_parity_on_carried_batches(estimators, ragged, mode):
    """Rows that are segments of longer traces, each with the carry of the
    commands before it: every impl honours the carry, row by row ('pallas'
    and 'reference' against 'vectorized', the golden tolerance)."""
    from repro.core.estimate_batch import TraceBatch
    from repro.serving.ring import segment
    rows, carries = [], []
    for tr in ragged:
        n = int(tr.n)
        bounds = [0, n // 3, 2 * n // 3, n]
        host = dram.CommandTrace(*(np.asarray(x) for x in tr))
        rows += [dram.CommandTrace(*(x[lo:hi] for x in host))
                 for lo, hi in zip(bounds[:-1], bounds[1:])]
        carries.append(segment(host, bounds).carry)
    batch = TraceBatch.from_traces(rows)
    carry = type(carries[0])(*(np.concatenate(f) for f in zip(*carries)))
    tb = TraceBatch(batch.trace, batch.weight, carry)
    assert carry.has_prev.any() and carry.open.any()
    kw = MODE_KW[mode]
    for est in estimators:
        base = est.estimate(tb, mode=mode, **kw)
        for impl in ("pallas", "reference"):
            other = est.estimate(tb, mode=mode, impl=impl, **kw)
            for b, o in zip(_reports(base, mode), _reports(other, mode)):
                for name, lb, lo in zip(b._fields, b, o):
                    np.testing.assert_allclose(
                        np.asarray(lo), np.asarray(lb), rtol=1e-5,
                        err_msg=f"{est.kind} mode={mode} impl={impl} "
                                f"leaf {name}")


def test_vendor_subset_parity(estimators, ragged):
    for est in estimators:
        full = est.estimate(ragged, impl="pallas")
        sub = est.estimate(ragged, (0, 2), impl="pallas")
        np.testing.assert_allclose(np.asarray(sub.energy_pj),
                                   np.asarray(full.energy_pj)[:, [0, 2]],
                                   rtol=1e-6, err_msg=est.kind)
        vec = est.estimate(ragged, (0, 2))
        np.testing.assert_allclose(np.asarray(sub.energy_pj),
                                   np.asarray(vec.energy_pj), rtol=1e-5,
                                   err_msg=est.kind)


def test_pad_rows_contribute_exactly_zero(quick_vampire):
    """Explicitly NOP/dt=0-padding a batch member to 3x its length must
    not change a single report leaf, on either batched impl — including
    per surface cell (pad NOPs land on cell (0, 0) and must add exactly
    zero charge AND zero cycles there)."""
    tr = idd_loops.validation_sweep(16)
    longer = idd_loops.validation_sweep(64)
    padded = dram.pad_trace(tr, 3 * tr.n)
    for impl in ("vectorized", "pallas"):
        for mode in ("mean", "surface"):
            a = quick_vampire.estimate([tr, longer], impl=impl, mode=mode)
            b = quick_vampire.estimate([padded, longer], impl=impl,
                                       mode=mode)
            for name, la, lb in zip(a._fields, a, b):
                np.testing.assert_allclose(
                    np.asarray(lb), np.asarray(la), rtol=1e-6,
                    err_msg=f"{impl} mode={mode} leaf {name}")


def test_batch_member_matches_solo_estimate(quick_vampire, ragged):
    """Each ragged member scored inside the padded batch == scored alone
    at its own (unpadded) shape, through the fused kernels."""
    rep = quick_vampire.estimate(ragged, impl="pallas")
    for i, tr in enumerate(ragged):
        one = quick_vampire.estimate([tr], impl="pallas")
        np.testing.assert_allclose(np.asarray(rep.energy_pj)[i],
                                   np.asarray(one.energy_pj)[0], rtol=1e-5)


def test_kernel_family_matches_its_ref_oracle(quick_vampire, ragged):
    """The pure-jnp oracle shipped beside the kernels
    (``vampire_energy/ref.batched_charge_ref``) pins the raw
    (charge, cycles) contract of ``ops.batched_charge_matrix``."""
    from repro.core.estimate_batch import TraceBatch
    from repro.kernels.vampire_energy import ops as vops
    from repro.kernels.vampire_energy import ref as vref
    tb = TraceBatch.from_traces(list(ragged))
    stacked = quick_vampire.fleet.params
    a_charge, a_cycles = vops.batched_charge_matrix(tb.trace, tb.weight,
                                                    stacked)
    b_charge, b_cycles = vref.batched_charge_ref(tb.trace, tb.weight,
                                                 stacked)
    np.testing.assert_allclose(np.asarray(a_charge), np.asarray(b_charge),
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(a_cycles),
                                  np.asarray(b_cycles))


def test_single_trace_kernel_shim_matches_batched(quick_vampire):
    """The legacy single-(trace, paramset) kernel entry point is a shim
    onto the batched kernel family."""
    from repro.kernels.vampire_energy.ops import trace_energy_kernel
    tr = idd_loops.validation_sweep(32)
    pp = quick_vampire.params(1)
    one = trace_energy_kernel(tr, pp)
    rep = quick_vampire.estimate([tr], (1,), impl="pallas")
    np.testing.assert_allclose(float(one.energy_pj),
                               np.asarray(rep.energy_pj)[0, 0], rtol=1e-6)


# ---------------------------------------------------------------------------
# Registry mechanics
# ---------------------------------------------------------------------------
def test_registry_resolution_and_errors():
    assert model_api.resolve_impl("scan").name == "reference"  # alias
    assert set(model_api.registered_impls()) >= {"vectorized", "pallas",
                                                 "reference"}
    for name in model_api.registered_impls():
        assert model_api.resolve_impl(name).name == name
    with pytest.raises(ValueError, match="unknown impl"):
        model_api.resolve_impl("typo")
    with pytest.raises(ValueError, match="unknown impl"):
        model_api.resolve_impl("kernel")  # the removed legacy entry point


def test_registry_accepts_new_impls_like_estimator_kinds():
    extra = model_api.EstimateImpl("test-only", "registry probe",
                                   modes=("mean",))
    model_api.register_impl(extra)
    try:
        assert model_api.resolve_impl("test-only") is extra
        assert "test-only" in model_api.registered_impls()
        with pytest.raises(ValueError, match="does not support mode"):
            model_api.resolve_impl("test-only", mode="range")
    finally:
        model_api._IMPLS.pop("test-only")


def test_estimate_rejects_unknown_impl(quick_vampire, ragged):
    with pytest.raises(ValueError, match="unknown impl"):
        quick_vampire.estimate(ragged, impl="typo")


def test_estimate_is_loud_for_registered_impl_without_a_path(quick_vampire,
                                                            estimators,
                                                            ragged):
    """Registering an impl does not give existing estimators a dispatch
    for it: estimate() must raise, never silently fall through to the
    reference oracle."""
    extra = model_api.EstimateImpl("no-path", "registry probe")
    model_api.register_impl(extra)
    try:
        for est in estimators:
            with pytest.raises(ValueError, match="no evaluation path"):
                est.estimate(ragged, impl="no-path")
    finally:
        model_api._IMPLS.pop("no-path")


# ---------------------------------------------------------------------------
# Platform detection / interpret fallback (kernels/common)
# ---------------------------------------------------------------------------
def test_interpret_default_resolves_per_call(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert kcommon.interpret_default() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert kcommon.interpret_default() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert kcommon.interpret_default() is (jax.default_backend() != "tpu")


def test_interpret_never_forced_on_tpu(monkeypatch):
    monkeypatch.setattr(kcommon.jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert kcommon.interpret_default() is False
    assert model_api.impl_execution_mode("pallas") == "compiled"


def test_impl_execution_mode_reports_fallback(monkeypatch):
    assert model_api.impl_execution_mode("vectorized") == "compiled"
    assert model_api.impl_execution_mode("reference") == "compiled"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert model_api.impl_execution_mode("pallas") == "interpret"
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert model_api.impl_execution_mode("pallas") == "compiled"


# ---------------------------------------------------------------------------
# Satellite wiring: kernel data ops + the campaign's fused path
# ---------------------------------------------------------------------------
def test_extract_structural_features_accepts_kernel_data_ops():
    """The popcount/toggle kernel ops wire into the shared feature pass
    and agree bit-for-bit with the jnp default."""
    from repro.core.energy_model import (extract_structural_features,
                                         kernel_data_ops)
    tr = traces.app_trace(traces.SPEC_APPS[2], n_requests=60)
    a = extract_structural_features(tr)
    b = extract_structural_features(tr, data_ops=kernel_data_ops())
    np.testing.assert_array_equal(np.asarray(a.ones), np.asarray(b.ones))
    np.testing.assert_array_equal(np.asarray(a.toggles),
                                  np.asarray(b.toggles))


def test_campaign_measures_identically_through_pallas(tiny_fleet):
    from repro.core import fleet as fleet_mod
    from repro.core.characterize import campaign_plan
    plan = campaign_plan(probe_reps=16, n_rows=4)
    mods = tiny_fleet[:4]
    a = fleet_mod.run_probes(mods, plan.idd_points, impl="vectorized")
    b = fleet_mod.run_probes(mods, plan.idd_points, impl="pallas")
    np.testing.assert_allclose(b, a, rtol=1e-5)
    with pytest.raises(ValueError, match="serial"):
        fleet_mod.run_probes(mods, plan.idd_points, impl="reference")
    # the serial oracle IS impl='reference'; asking it for the fused path
    # must be loud, not silently oracle-measured
    with pytest.raises(ValueError, match="batched"):
        fleet_mod.run_probes(mods, plan.idd_points, engine="serial",
                             impl="pallas")
