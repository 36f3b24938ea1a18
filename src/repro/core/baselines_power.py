"""State-of-the-art baseline DRAM power models the paper validates against
(Section 9.1): the Micron power calculator (TN-41-01) and DRAMPower.

Both are IDD/datasheet-driven. We implement them *faithfully to their
documented flaws* (as characterized in the paper and in [26, 65]):

Micron model:
  * uses worst-case datasheet IDD values;
  * background power assumes the device is in the all-banks-active state
    whenever the trace is active (does not track the number of open banks);
  * activate/precharge power is computed from IDD0 at the *specification*
    command spacing (tRC), not the actual spacing in the trace;
  * no data dependency, no structural variation, no process variation.

DRAMPower:
  * uses datasheet IDD values, but integrates with the *actual* command
    timing from the trace;
  * background state tracked as precharged (IDD2N) vs. >=1 bank active
    (IDD3N) — not per-bank;
  * read/write energies from IDD4R/IDD4W over the actual burst windows;
  * no data dependency, no structural variation.

Both are exposed two ways:

* the per-trace functions :func:`micron_power` / :func:`drampower`
  (one trace, one datasheet dict) — the paper-figure form;
* :class:`MicronModel` / :class:`DRAMPowerModel`, estimators implementing
  the unified protocol (``repro.core.model_api``): pytree-native (the
  stacked (vendors, keys) IDD table is the array leaf), scored over a
  padded :class:`~repro.core.estimate_batch.TraceBatch` through the SAME
  shared structural-feature pass as VAMPIRE, one vmapped dispatch per
  (traces x vendors) grid.

Neither baseline models data dependency or process variation — that is the
paper's point — so ``mode='distribution'`` degenerates to ``'mean'`` (the
ones/toggle fractions cannot matter) and ``mode='range'`` returns a
collapsed (mean, mean, mean) band.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import model_api
from repro.core.dram import (ACT, RD, WR, REF, CommandTrace, TIMING)
from repro.core.energy_model import (BG_ACTIVE, BG_PDN_ACT, BG_PDN_FAST,
                                     BG_PDN_SLOW, EnergyReport,
                                     StructuralFeatures, _report,
                                     extract_structural_features,
                                     surface_charge, surface_cycles)

_T = TIMING

# datasheet keys the baseline formulas consume, in stacked-table order;
# the low-power keys are appended at the END so stacked tables saved
# before the background-state lattice keep their column meaning
BASELINE_IDD_KEYS = ("IDD0", "IDD2N", "IDD2P1", "IDD3N", "IDD4R", "IDD4W",
                     "IDD5B", "IDD2P0", "IDD3P", "IDD6")
_LOWPOWER_KEYS = ("IDD2P0", "IDD3P", "IDD6")


def with_lowpower_defaults(ds) -> dict:
    """Datasheet dicts predating the background-state lattice lack the
    low-power keys; default them to the fast power-down current (the old
    models' single power-down rate), keeping old blobs loadable."""
    if all(k in ds for k in _LOWPOWER_KEYS):
        return dict(ds)
    out = dict(ds)
    for k in _LOWPOWER_KEYS:
        out.setdefault(k, out["IDD2P1"])
    return out


def _bg_state(sf: StructuralFeatures):
    """The two structural facts both baselines consume, from the shared
    param-independent feature pass: per-command open-bank count and the
    background-state code (BG_*)."""
    return jnp.sum(sf.open_before.astype(jnp.float32), axis=1), sf.bg_state


def _bg_lut(bg_state, i_active, ds):
    """Background current from the state code — the baselines' datasheet
    LUT twin of :func:`energy_model.background_current`."""
    i_low = jnp.where(bg_state == BG_PDN_FAST, ds["IDD2P1"],
                      jnp.where(bg_state == BG_PDN_SLOW, ds["IDD2P0"],
                                jnp.where(bg_state == BG_PDN_ACT,
                                          ds["IDD3P"], ds["IDD6"])))
    return jnp.where(bg_state == BG_ACTIVE, i_active, i_low)


def act_pair_charge(idd0, idd2n, idd3n) -> jax.Array:
    """ACT/PRE pair charge above the active background, from IDD0 at the
    specification row-cycle — the ONE definition of this physics, shared
    by both baselines here and by the fused ``kernels/baseline_energy``
    kernel (so ``impl='pallas'`` cannot drift from ``'vectorized'``)."""
    return jnp.maximum(
        (idd0 - (idd3n * _T.tRAS + idd2n * _T.tRP) / _T.tRC) * _T.tRC, 0.0)


def _act_pair_charge(ds) -> jax.Array:
    return act_pair_charge(ds["IDD0"], ds["IDD2N"], ds["IDD3N"])


def micron_charges(trace: CommandTrace, open_banks, bg_state,
                   ds, carried_act=False) -> jax.Array:
    """Per-command charge (mA*cycles) of the TN-41-01-style estimate.
    ``ds`` maps IDD key -> current; values broadcast against the trace.
    ``carried_act``: a segment's carry says the whole trace issues an
    ACT (``SegmentCarry.any_act``)."""
    del open_banks  # the calculator's documented flaw: bank count ignored
    dt = trace.dt.astype(jnp.float32)
    # Worst-case background: all-banks-active current whenever not in a
    # low-power state (the flaw reported by [65] and Section 9.1).
    i_bg = _bg_lut(bg_state, ds["IDD3N"], ds)
    charge = i_bg * dt
    # ACT/PRE power at the *specification* row-cycling rate: the calculator
    # charges one ACT/PRE pair per spec tRC of active time, regardless of the
    # actual command spacing in the trace ([26]'s "does not account for any
    # additional time that may elapse between two DRAM commands").
    q_act = _act_pair_charge(ds)
    any_act = jnp.any(trace.cmd == ACT) | carried_act
    charge = charge + jnp.where((bg_state == BG_ACTIVE) & any_act,
                                q_act * dt / _T.tRC, 0.0)
    # Read/write power stacked on the (already worst-case) background — the
    # calculator's documented mishandling of bank-state/command interaction
    # ([65]; Section 9.1: "significantly overestimates the power").
    burst = jnp.minimum(dt, float(_T.tBURST))
    charge = charge + jnp.where(trace.cmd == RD, ds["IDD4R"] * burst, 0.0)
    charge = charge + jnp.where(trace.cmd == WR, ds["IDD4W"] * burst, 0.0)
    charge = charge + jnp.where(
        trace.cmd == REF, (ds["IDD5B"] - ds["IDD2N"]) * _T.tRFC, 0.0)
    return charge


def drampower_charges(trace: CommandTrace, open_banks, bg_state,
                      ds, carried_act=False) -> jax.Array:
    """Per-command charge (mA*cycles) of the DRAMPower-style estimate:
    datasheet IDDs, actual timing (``carried_act`` is unused: its ACT
    charge follows the trace's own ACTs)."""
    dt = trace.dt.astype(jnp.float32)
    # Bank-sensitive background (DRAMPower includes the [65, 107] extension:
    # linear interpolation between IDD2N and IDD3N by open-bank count), but
    # with datasheet values and no per-bank structure.
    i_bg = _bg_lut(
        bg_state,
        ds["IDD2N"] + (ds["IDD3N"] - ds["IDD2N"]) * open_banks / 8.0, ds)
    charge = i_bg * dt
    charge = charge + jnp.where(trace.cmd == ACT, _act_pair_charge(ds), 0.0)
    burst = jnp.minimum(dt, float(_T.tBURST))
    charge = charge + jnp.where(
        trace.cmd == RD, (ds["IDD4R"] - i_bg) * burst, 0.0)
    charge = charge + jnp.where(
        trace.cmd == WR, (ds["IDD4W"] - i_bg) * burst, 0.0)
    charge = charge + jnp.where(
        trace.cmd == REF, (ds["IDD5B"] - ds["IDD2N"]) * _T.tRFC, 0.0)
    return charge


_CHARGE_FNS = {"micron": micron_charges, "drampower": drampower_charges}


def _carried_act(carry) -> jax.Array | bool:
    return False if carry is None else carry.any_act


def micron_power(trace: CommandTrace, ds: dict[str, float],
                 carry=None) -> EnergyReport:
    """TN-41-01-style estimate from datasheet IDDs (single trace, or one
    segment of one with its ``carry``)."""
    ds = with_lowpower_defaults(ds)
    ob, pd = _bg_state(extract_structural_features(trace, carry=carry))
    charge = micron_charges(trace, ob, pd,
                            {k: jnp.float32(ds[k]) for k in BASELINE_IDD_KEYS},
                            _carried_act(carry))
    return _report(jnp.sum(charge), trace.total_cycles())


def drampower(trace: CommandTrace, ds: dict[str, float],
              carry=None) -> EnergyReport:
    """DRAMPower-style estimate: datasheet IDDs, actual timing (single
    trace, or one segment of one with its ``carry``)."""
    ds = with_lowpower_defaults(ds)
    ob, pd = _bg_state(extract_structural_features(trace, carry=carry))
    charge = drampower_charges(
        trace, ob, pd, {k: jnp.float32(ds[k]) for k in BASELINE_IDD_KEYS})
    return _report(jnp.sum(charge), trace.total_cycles())


MODELS = {"micron": micron_power, "drampower": drampower}


# ---------------------------------------------------------------------------
# Batched dispatches (one per baseline, shared skeleton)
# ---------------------------------------------------------------------------
def _batched_baseline(charge_fn):
    @jax.jit
    def dispatch(trace: CommandTrace, weight: jax.Array,
                 table: jax.Array, carry=None) -> EnergyReport:
        """Energy reports of every (trace, vendor) pair in one dispatch.
        ``trace``/``weight`` are a TraceBatch's padded fields; ``table`` is
        the stacked (vendors, len(BASELINE_IDD_KEYS)) datasheet matrix;
        ``carry`` the rows' segment carries (None for whole traces)."""
        def one_trace(tr: CommandTrace, w: jax.Array, c):
            ob, pd = _bg_state(extract_structural_features(tr, carry=c))
            cycles = jnp.sum(tr.dt * w.astype(jnp.int32), dtype=jnp.int32)

            def one_vendor(row):
                ds = {k: row[i] for i, k in enumerate(BASELINE_IDD_KEYS)}
                return jnp.sum(charge_fn(tr, ob, pd, ds, _carried_act(c))
                               * w)

            return jax.vmap(one_vendor)(table), cycles

        charge, cycles = jax.vmap(one_trace)(trace, weight, carry)
        return _report(charge,
                       jnp.broadcast_to(cycles[:, None], charge.shape))
    return dispatch


_BATCHED = {kind: _batched_baseline(fn) for kind, fn in _CHARGE_FNS.items()}


def _batched_baseline_surface(charge_fn):
    @jax.jit
    def dispatch(trace: CommandTrace, weight: jax.Array,
                 table: jax.Array, carry=None) -> EnergyReport:
        """``mode='surface'`` twin of the mean dispatch: the identical
        per-command charges grouped onto the (bank, row-band) cells ->
        (traces, vendors, banks, row_bands)-shaped report leaves.  The
        baselines model no structural variation — that is the paper's
        point — so their surfaces are flat in everything but workload
        placement; the decomposition is what exposes that flatness next
        to VAMPIRE's."""
        def one_trace(tr: CommandTrace, w: jax.Array, c):
            ob, pd = _bg_state(extract_structural_features(tr, carry=c))

            def one_vendor(row):
                ds = {k: row[i] for i, k in enumerate(BASELINE_IDD_KEYS)}
                return surface_charge(
                    tr, w, charge_fn(tr, ob, pd, ds, _carried_act(c)))

            return jax.vmap(one_vendor)(table), surface_cycles(tr, w)

        charge, cycles = jax.vmap(one_trace)(trace, weight, carry)
        return _report(charge,
                       jnp.broadcast_to(cycles[:, None], charge.shape))
    return dispatch


_BATCHED_SURFACE = {kind: _batched_baseline_surface(fn)
                    for kind, fn in _CHARGE_FNS.items()}


# ---------------------------------------------------------------------------
# Protocol estimators
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DatasheetModel(model_api.StackedEstimatorMixin):
    """Base of the baseline estimators: per-vendor datasheet IDD values as
    one stacked pytree leaf, scored through the shared batched engine."""
    datasheets: dict[int, dict[str, float]]
    idd_table: jax.Array = None  # type: ignore  # (V, K) float32 leaf

    kind = None  # class attribute (NOT a field), overridden per subclass

    def __post_init__(self):
        self.datasheets = {v: with_lowpower_defaults(d)
                           for v, d in self.datasheets.items()}
        if self.idd_table is None:
            self.idd_table = jnp.asarray(
                [[self.datasheets[v][k] for k in BASELINE_IDD_KEYS]
                 for v in sorted(self.datasheets)], jnp.float32)
        elif self.idd_table.shape[-1] < len(BASELINE_IDD_KEYS):
            # stacked table saved before the background-state lattice:
            # pad the missing low-power columns with the IDD2P1 column
            pd_col = self.idd_table[:, BASELINE_IDD_KEYS.index("IDD2P1")]
            pad = jnp.tile(pd_col[:, None],
                           (1, len(BASELINE_IDD_KEYS)
                            - self.idd_table.shape[-1]))
            self.idd_table = jnp.concatenate([self.idd_table, pad], axis=-1)

    # ------------------------------------------------------- construction
    @classmethod
    def from_datasheets(cls, datasheets: dict[int, dict[str, float]]):
        return cls(datasheets={v: dict(d) for v, d in datasheets.items()})

    @classmethod
    def from_vampire(cls, model):
        """Share the fitted VAMPIRE model's derived per-vendor datasheets
        (what the vendor would publish; paper Section 4)."""
        return cls.from_datasheets(
            {v: model.by_vendor[v].idd_datasheet for v in model.by_vendor})

    @property
    def vendors(self) -> tuple[int, ...]:
        return tuple(sorted(self.datasheets))

    def _table_for(self, idx: tuple[int, ...]) -> jax.Array:
        if idx == tuple(range(self.idd_table.shape[0])):
            return self.idd_table
        return self._memo_subset(
            idx, self.idd_table,
            lambda: self.idd_table[jnp.asarray(idx, jnp.int32)])

    # ----------------------------------------------------------- estimate
    def estimate(self, traces, vendors=None, *,
                 mode: model_api.EstimateMode = "mean",
                 impl: str = "vectorized", data=None,
                 ones_frac=None, toggle_frac=None):
        """Unified protocol entry point.  ``mode='distribution'`` equals
        ``'mean'`` (no data dependency to feed the fractions into) and
        ``mode='range'`` collapses to (mean, mean, mean) — these baselines
        model neither, which is Section 9.1's finding.  ``mode='surface'``
        returns the (traces, vendors, banks, row_bands) decomposition of
        the same charges: structurally flat (the physics has no
        per-bank/row terms), varying only with workload placement — the
        contrast against VAMPIRE's surfaces.  ``impl`` resolves
        through the shared registry: ``'vectorized'`` (one vmapped
        dispatch), ``'pallas'`` (the fused baseline-energy kernel gridded
        over vendors), ``'reference'`` (the pair-at-a-time per-trace
        functions ``micron_power``/``drampower``)."""
        # one shared argument contract across every estimator: fractions
        # (typed DataProfile or the loose kwargs) are required WITH
        # mode='distribution' (even though this physics ignores their
        # values) and rejected without it
        profile = model_api.normalize_data_profile(data, ones_frac,
                                                   toggle_frac)
        model_api.validate_data_profile(mode, profile)
        impl = model_api.resolve_impl(impl, mode=mode).name
        model_api.require_impl_path(self.kind, impl,
                                    ("vectorized", "pallas", "reference"))
        _, idx = model_api.resolve_vendor_indices(self.vendors, vendors)
        tb = self._batch_cache.get(traces)
        if mode == "surface":
            if impl == "vectorized":
                return _BATCHED_SURFACE[self.kind](tb.trace, tb.weight,
                                                   self._table_for(idx),
                                                   tb.carry)
            if impl == "pallas":
                from repro.kernels.baseline_energy import ops as bops
                charge, cycles = bops.baseline_charge_matrix(
                    tb.trace, tb.weight, self._table_for(idx), self.kind,
                    surface=True, carry=tb.carry)
                return _report(charge, jnp.broadcast_to(cycles[:, None],
                                                        charge.shape))
            return self._reference_surface(traces, tb, idx)
        if impl == "vectorized":
            rep = _BATCHED[self.kind](tb.trace, tb.weight,
                                      self._table_for(idx), tb.carry)
        elif impl == "pallas":
            from repro.kernels.baseline_energy import ops as bops
            charge, cycles = bops.baseline_charge_matrix(
                tb.trace, tb.weight, self._table_for(idx), self.kind,
                carry=tb.carry)
            rep = _report(charge,
                          jnp.broadcast_to(cycles[:, None], charge.shape))
        else:
            rep = self._reference_matrix(traces, tb, idx)
        if mode == "range":
            return rep, rep, rep
        return rep

    def _reference_surface(self, traces, tb, idx) -> EnergyReport:
        """``impl='reference'`` for ``mode='surface'``: the paper-figure
        per-trace charge formulas, grouped onto the (bank, row-band) cells
        one (trace, vendor) pair at a time."""
        from repro.core.estimate_batch import original_traces, row_carries
        originals = original_traces(traces, tb)
        order = self.vendors
        charge_fn = _CHARGE_FNS[self.kind]
        per_trace = []
        for tr, c in zip(originals, row_carries(tb)):
            ob, pd = _bg_state(extract_structural_features(tr, carry=c))
            w = jnp.ones(tr.n, jnp.float32)
            pairs = []
            for j in idx:
                ds = {k: jnp.float32(self.datasheets[order[j]][k])
                      for k in BASELINE_IDD_KEYS}
                pairs.append(_report(
                    surface_charge(tr, w, charge_fn(tr, ob, pd, ds,
                                                    _carried_act(c))),
                    surface_cycles(tr, w)))
            per_trace.append(jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves), *pairs))
        return jax.tree_util.tree_map(lambda *rows: jnp.stack(rows),
                                      *per_trace)

    def _reference_matrix(self, traces, tb, idx) -> EnergyReport:
        """``impl='reference'``: the paper-figure per-trace functions
        (``micron_power``/``drampower``), one call per (trace, vendor)."""
        from repro.core.estimate_batch import original_traces, row_carries
        originals = original_traces(traces, tb)
        order = self.vendors
        fn = MODELS[self.kind]
        per_trace = [
            jax.tree_util.tree_map(
                lambda *leaves: jnp.stack(leaves),
                *[fn(tr, self.datasheets[order[j]], c) for j in idx])
            for tr, c in zip(originals, row_carries(tb))]
        return jax.tree_util.tree_map(lambda *rows: jnp.stack(rows),
                                      *per_trace)

    # ----------------------------------------------------------------- io
    def save(self, path: str, *, meta: dict | None = None):
        model_api.save_estimator(self, path, meta=meta)

    @classmethod
    def load(cls, path: str):
        model = model_api.load_estimator(path)
        if not isinstance(model, cls):
            raise TypeError(f"{path} holds a {type(model).__name__}, "
                            f"not a {cls.__name__}")
        return model


@dataclasses.dataclass
class MicronModel(DatasheetModel):
    kind = "micron"


@dataclasses.dataclass
class DRAMPowerModel(DatasheetModel):
    kind = "drampower"


def _baseline_flatten(m):
    return (m.idd_table,), (m._aux_static(m.datasheets),)


def _make_baseline_unflatten(cls):
    def unflatten(aux, children):
        m = object.__new__(cls)
        m.datasheets = aux[0].value
        m.idd_table = children[0]
        m.__dict__["_aux"] = aux[0]   # stable treedefs across round trips
        return m
    return unflatten


for _cls in (MicronModel, DRAMPowerModel):
    jax.tree_util.register_pytree_node(_cls, _baseline_flatten,
                                       _make_baseline_unflatten(_cls))

BASELINE_MODELS = {"micron": MicronModel, "drampower": DRAMPowerModel}
