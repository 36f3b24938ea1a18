"""VAMPIRE — Variation-Aware model of Memory Power Informed by Real
Experiments (paper Section 9), fitted from the characterization campaign.

Public API (the unified estimator protocol, ``repro.core.model_api``)
---------------------------------------------------------------------
``Vampire.fit(fleet)``       run the campaign and build the model — a thin
    shim onto ``model_api.fit('vampire', fleet, fitter='campaign')``, the
    registry-routed fitting entry point (``fitter='streaming'`` is the
    online-recalibration path, ``repro.core.recalibrate``).
``model.estimate(traces, vendors=None, *, mode='mean', impl='vectorized',
                 data=DataProfile(...) | None,
                 ones_frac=None, toggle_frac=None)``
    ONE entry point for every estimation question.  ``traces`` is a single
    trace, a sequence of ragged traces, or a prebuilt
    ``estimate_batch.TraceBatch``; the full (traces x vendors) report
    matrix is evaluated in one jitted ``vmap(vmap)`` dispatch and every
    leaf of the returned ``EnergyReport`` has shape ``(traces, vendors)``.

    * ``mode='mean'``          the report matrix.
    * ``mode='range'``         (lo, mean, hi) matrices across each vendor's
      process-variation band (captured from the per-module IDD spread).
    * ``mode='distribution'``  the paper's no-data-trace mode: the caller
      supplies ``ones_frac``/``toggle_frac`` (scalar or per trace) instead
      of actual 64-byte values.
    * ``mode='surface'``       the structural-variation decomposition
      (paper Section 6 / Figs 19-22): report leaves are ``(traces,
      vendors, banks, row_bands)``-shaped, each command's charge grouped
      onto its (bank, row-band) cell; summing the cell axes recovers
      ``mode='mean'`` exactly.
    * ``impl`` resolves through the registry (``model_api.resolve_impl``):
      ``'vectorized'`` is the jnp/XLA batched engine, ``'pallas'`` the
      fused (traces x vendors) Pallas kernel family (compiled on TPU,
      interpret-mode elsewhere), and ``'reference'`` (alias ``'scan'``)
      the pair-at-a-time per-command oracle kept for cross-checking.

``model.save(path)`` / ``Vampire.load(path)``
    schema-v2 ``.npz`` + JSON-manifest serialization; v1 pickle blobs
    still load with a ``DeprecationWarning`` (``repro.core.model_api``).

The model IS a pytree
---------------------
The fitted state lives in a :class:`FleetModel`: per-vendor
:class:`PowerParams` stacked once at fit time along a leading vendor axis,
with the variation bands, datasheet IDD tables, and vendor ids as array
leaves.  ``Vampire`` itself is a registered pytree whose children are those
leaves (the raw characterization record rides along as static aux data), so
a fitted model can be passed straight through ``jax.jit`` / ``jax.vmap`` /
``jax.device_put`` — e.g. ``jax.jit(lambda m: m.estimate(batch))(model)``
compiles with the model as a traced argument.

The pre-unification methods (``estimate(trace, vendor)`` positional,
``estimate_range``, ``estimate_distribution`` and their ``*_many``
variants) remain as thin shims that delegate to ``estimate`` and emit
``DeprecationWarning``.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import characterize, device_sim, model_api
from repro.core.dram import CommandTrace
from repro.core.energy_model import (EnergyReport, PowerParams, _report,
                                     charge_from_features,
                                     distribution_features,
                                     extract_structural_features,
                                     finalize_features, scale_report,
                                     surface_charge, surface_cycles,
                                     trace_charges_scan, trace_energy_scan)
from repro.core.fleet import stack_params


class FleetModel(NamedTuple):
    """The pytree-native fitted state: every leaf carries a leading vendor
    axis, so the whole bundle jits, vmaps, and shards as one unit."""
    params: PowerParams        # stacked (V, ...) fitted per-vendor params
    band: jax.Array            # (V, 2) multiplicative (lo, hi) variation
    idd_datasheet: jax.Array   # (V, K) datasheet IDDs (keys in `idd_keys`)
    vendor_ids: jax.Array      # (V,) int32


def _squeeze_pair(rep: EnergyReport) -> EnergyReport:
    """(1, 1)-shaped report matrix -> scalar-leaf report (legacy shape)."""
    return jax.tree_util.tree_map(lambda x: x[0, 0], rep)


def _shim_warning(old: str, new: str):
    warnings.warn(
        f"Vampire.{old} is deprecated; call Vampire.{new} instead "
        "(the unified estimator protocol, repro.core.model_api).",
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass
class Vampire(model_api.StackedEstimatorMixin):
    by_vendor: dict[int, characterize.VendorCharacterization]
    # multiplicative process-variation band per vendor (lo, hi) captured from
    # the spread of per-module IDD measurements during characterization
    variation_band: dict[int, tuple[float, float]] = None  # type: ignore

    kind = "vampire"

    def __post_init__(self):
        if self.variation_band is None:
            self.variation_band = {}
            for v, vc in self.by_vendor.items():
                rel = []
                for key in ("IDD0", "IDD4R", "IDD4W"):
                    arr = vc.idd_measured[key]
                    rel.append(arr / np.mean(arr))
                rel = np.concatenate(rel)
                self.variation_band[v] = (float(np.min(rel)),
                                          float(np.max(rel)))

    # ------------------------------------------------------------------ fit
    @classmethod
    def fit(cls, fleet=None, **kw) -> "Vampire":
        """Run the characterization campaign and build the model.

        Thin shim onto ``model_api.fit('vampire', fleet,
        fitter='campaign', **kw)`` — the registry-routed fitting entry
        point; bit-for-bit identical to the pre-registry fit.

        ``engine='batched'`` (default) runs the campaign through the vmapped
        fleet engine (``repro.core.fleet``); ``engine='serial'`` replays it
        one measurement at a time (the correctness oracle)."""
        return model_api.fit("vampire", fleet, fitter="campaign", **kw)

    @property
    def vendors(self) -> tuple[int, ...]:
        return tuple(sorted(self.by_vendor))

    def params(self, vendor: int) -> PowerParams:
        return self.by_vendor[vendor].fitted

    # -------------------------------------------------- the pytree bundle
    @property
    def fleet(self) -> FleetModel:
        fm = self.__dict__.get("_fleet")
        if fm is None:
            fm = self._build_fleet()
            self.__dict__["_fleet"] = fm
        return fm

    def _build_fleet(self) -> FleetModel:
        vs = self.vendors
        for v in vs:
            if self.by_vendor[v].fitted is None:
                self.by_vendor[v].build_params()
        idd_keys = sorted(self.by_vendor[vs[0]].idd_datasheet)
        return FleetModel(
            params=stack_params([self.by_vendor[v].fitted for v in vs]),
            band=jnp.asarray([self.variation_band[v] for v in vs],
                             jnp.float32),
            idd_datasheet=jnp.asarray(
                [[self.by_vendor[v].idd_datasheet[k] for k in idd_keys]
                 for v in vs], jnp.float32),
            vendor_ids=jnp.asarray(vs, jnp.int32))

    def _stacked_for(self, idx: tuple[int, ...]):
        """(stacked params, band) rows for the requested vendor indices;
        subsets are sliced once and memoized per vendor tuple
        (``model_api.StackedEstimatorMixin``)."""
        fm = self.fleet
        if idx == tuple(range(fm.band.shape[0])):
            return fm.params, fm.band

        def build():
            sel = jnp.asarray(idx, jnp.int32)
            return (jax.tree_util.tree_map(lambda x: x[sel], fm.params),
                    fm.band[sel])

        return self._memo_subset(idx, fm, build)

    # ------------------------------------------------------------- estimate
    def estimate(self, traces, vendors=None, *legacy_impl,
                 mode: model_api.EstimateMode = "mean",
                 impl: str = "vectorized", data=None,
                 ones_frac=None, toggle_frac=None):
        """The unified entry point (see the module docstring).

        NOTE: portable protocol code must pass ``vendors`` as a sequence
        (or ``None``).  The (single trace, bare int vendor) call shape is
        reserved for the legacy ``estimate(trace, vendor)`` form — it
        emits ``DeprecationWarning`` and returns the historical
        scalar-leaf report rather than a (1, 1) matrix."""
        if legacy_impl or (isinstance(traces, CommandTrace)
                           and isinstance(vendors, (int, np.integer))):
            if not (isinstance(traces, CommandTrace)
                    and isinstance(vendors, (int, np.integer))):
                raise TypeError("positional impl is only accepted by the "
                                "legacy estimate(trace, vendor, impl) form "
                                "(one CommandTrace, one int vendor)")
            if mode != "mean" or data is not None \
                    or ones_frac is not None or toggle_frac is not None:
                # the legacy form is mean-mode only; silently forcing
                # mode='mean' here would return numerically wrong results
                raise TypeError(
                    "the legacy estimate(trace, vendor) form does not "
                    "accept mode/ones_frac/toggle_frac; pass vendors as a "
                    "sequence, e.g. estimate([trace], (vendor,), mode=...)")
            _shim_warning("estimate(trace, vendor)",
                          "estimate(traces, vendors)")
            impl = legacy_impl[0] if legacy_impl else impl
            return _squeeze_pair(self._estimate(
                traces, (int(vendors),), mode="mean", impl=impl))
        return self._estimate(traces, vendors, mode=mode, impl=impl,
                              data=data, ones_frac=ones_frac,
                              toggle_frac=toggle_frac)

    def _estimate(self, traces, vendors=None, *, mode="mean",
                  impl="vectorized", data=None, ones_frac=None,
                  toggle_frac=None):
        from repro.core import estimate_batch
        profile = model_api.normalize_data_profile(data, ones_frac,
                                                   toggle_frac)
        model_api.validate_data_profile(mode, profile)
        ones_frac, toggle_frac = profile.ones_frac, profile.toggle_frac
        impl = model_api.resolve_impl(impl, mode=mode).name
        model_api.require_impl_path(self.kind, impl,
                                    ("vectorized", "pallas", "reference"))
        _, idx = model_api.resolve_vendor_indices(self.vendors, vendors)
        stacked, band = self._stacked_for(idx)
        tb = self._batch_cache.get(traces)

        carry = tb.carry
        if mode == "surface":
            if impl == "vectorized":
                return estimate_batch.batched_surface_reports(
                    tb.trace, tb.weight, stacked, carry)
            if impl == "pallas":
                return estimate_batch.pallas_batched_surface_reports(
                    tb.trace, tb.weight, stacked, carry)
            return self._reference_surface(traces, tb, stacked)

        if mode == "distribution":
            if impl == "vectorized":
                return estimate_batch.batched_distribution_reports(
                    tb.trace, tb.weight, stacked,
                    jnp.asarray(ones_frac, jnp.float32),
                    jnp.asarray(toggle_frac, jnp.float32), carry)
            if impl == "pallas":
                return estimate_batch.pallas_batched_distribution_reports(
                    tb.trace, tb.weight, stacked, ones_frac, toggle_frac,
                    carry)
            return self._reference_matrix(traces, tb, stacked,
                                          ones_frac=ones_frac,
                                          toggle_frac=toggle_frac)

        if impl == "vectorized":
            if mode == "range":
                return estimate_batch.batched_range_reports(
                    tb.trace, tb.weight, stacked, band, carry)
            return estimate_batch.batched_reports(tb.trace, tb.weight,
                                                  stacked, carry)
        if impl == "pallas":
            if mode == "range":
                return estimate_batch.pallas_batched_range_reports(
                    tb.trace, tb.weight, stacked, band, carry)
            return estimate_batch.pallas_batched_reports(tb.trace, tb.weight,
                                                         stacked, carry)
        mean = self._reference_matrix(traces, tb, stacked)
        if mode == "mean":
            return mean
        lo = scale_report(mean, band[None, :, 0])
        hi = scale_report(mean, band[None, :, 1])
        return lo, mean, hi

    def _reference_matrix(self, traces, tb, stacked: PowerParams, *,
                          ones_frac=None, toggle_frac=None) -> EnergyReport:
        """``impl='reference'``: the pair-at-a-time oracle — the lax.scan
        per-command state machine for measured-data modes, the per-trace
        feature-override path for ``mode='distribution'``.  A carried
        batch's rows start from their carries."""
        from repro.core.estimate_batch import original_traces, row_carries
        originals = original_traces(traces, tb)
        carries = row_carries(tb)
        if ones_frac is not None:
            of = np.broadcast_to(np.asarray(ones_frac, np.float32),
                                 (len(originals),))
            tf = np.broadcast_to(np.asarray(toggle_frac, np.float32),
                                 (len(originals),))

            def one_pair(tr, pp, i):
                sf = distribution_features(
                    extract_structural_features(tr, carry=carries[i]),
                    of[i], tf[i])
                charges = charge_from_features(
                    tr, finalize_features(sf, pp), pp)
                return _report(jnp.sum(charges), tr.total_cycles())

            per_trace = [jax.vmap(lambda pp, tr=tr, i=i: one_pair(tr, pp, i)
                                  )(stacked)
                         for i, tr in enumerate(originals)]
        else:
            per_trace = [jax.vmap(lambda pp, tr=tr, c=c:
                                  trace_energy_scan(tr, pp, c))(stacked)
                         for tr, c in zip(originals, carries)]
        return jax.tree_util.tree_map(lambda *rows: jnp.stack(rows),
                                      *per_trace)

    def _reference_surface(self, traces, tb, stacked: PowerParams
                           ) -> EnergyReport:
        """``impl='reference'`` for ``mode='surface'``: the per-command
        lax.scan oracle's charge stream, grouped onto the (bank, row-band)
        cells one (trace, vendor) pair at a time."""
        from repro.core.estimate_batch import original_traces, row_carries
        originals = original_traces(traces, tb)

        def one_pair(tr, pp, c):
            charges = trace_charges_scan(tr, pp, c)
            w = jnp.ones_like(charges)
            return _report(surface_charge(tr, w, charges),
                           surface_cycles(tr, w))

        per_trace = [jax.vmap(lambda pp, tr=tr, c=c: one_pair(tr, pp, c))(
            stacked) for tr, c in zip(originals, row_carries(tb))]
        return jax.tree_util.tree_map(lambda *rows: jnp.stack(rows),
                                      *per_trace)

    # --------------------------------------------------- deprecated shims
    def estimate_range(self, trace: CommandTrace, vendor: int,
                       impl: str = "vectorized"
                       ) -> tuple[EnergyReport, EnergyReport, EnergyReport]:
        _shim_warning("estimate_range", "estimate(..., mode='range')")
        return tuple(_squeeze_pair(r) for r in self._estimate(
            trace, (int(vendor),), mode="range", impl=impl))

    def estimate_distribution(self, trace: CommandTrace, vendor: int,
                              ones_frac: float, toggle_frac: float
                              ) -> EnergyReport:
        _shim_warning("estimate_distribution",
                      "estimate(..., mode='distribution')")
        return _squeeze_pair(self._estimate(
            trace, (int(vendor),), mode="distribution",
            ones_frac=ones_frac, toggle_frac=toggle_frac))

    def estimate_many(self, traces, vendors=None) -> EnergyReport:
        _shim_warning("estimate_many", "estimate")
        return self._estimate(traces, vendors)

    def estimate_range_many(self, traces, vendors=None
                            ) -> tuple[EnergyReport, EnergyReport,
                                       EnergyReport]:
        _shim_warning("estimate_range_many", "estimate(..., mode='range')")
        return self._estimate(traces, vendors, mode="range")

    def estimate_distribution_many(self, traces, vendors=None, *,
                                   ones_frac, toggle_frac) -> EnergyReport:
        _shim_warning("estimate_distribution_many",
                      "estimate(..., mode='distribution')")
        return self._estimate(traces, vendors, mode="distribution",
                              ones_frac=ones_frac, toggle_frac=toggle_frac)

    # ------------------------------------------------------------------ io
    def save(self, path: str, *, meta: dict | None = None):
        """Schema-v2 ``.npz`` + JSON-manifest blob (``repro.core.model_api``);
        round-trips the fitted params, bands, datasheets, and — when present
        — the raw campaign sweeps the benchmarks plot."""
        model_api.save_estimator(self, path, meta=meta)

    @classmethod
    def load(cls, path: str) -> "Vampire":
        """Load a ``save`` blob (v2 ``.npz``, or a v1 pickle with a
        ``DeprecationWarning``)."""
        model = model_api.load_estimator(path)
        if not isinstance(model, cls):
            raise TypeError(f"{path} holds a {type(model).__name__}, "
                            "not a Vampire model")
        return model


def _vampire_flatten(m: Vampire):
    return (m.fleet,), (m._aux_static((m.by_vendor, m.variation_band)),)


def _vampire_unflatten(aux, children) -> Vampire:
    m = object.__new__(Vampire)
    by_vendor, band = aux[0].value
    m.by_vendor = by_vendor
    m.variation_band = band
    m.__dict__["_fleet"] = children[0]
    m.__dict__["_aux"] = aux[0]   # keep treedefs equal across round trips
    return m


jax.tree_util.register_pytree_node(Vampire, _vampire_flatten,
                                   _vampire_unflatten)


def reference_vampire() -> Vampire:
    """A quick-fit VAMPIRE on a reduced fleet (for tests/examples)."""
    from repro.core import params as P
    fleet = device_sim.make_fleet(
        [P.ModuleSpec(v, i, 2015) for v in range(3) for i in range(3)])
    return Vampire.fit(fleet, probe_modules=2, probe_reps=64, n_rows=8)
