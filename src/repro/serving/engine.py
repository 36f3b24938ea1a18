"""The serving mesh engine: resident model + sharded batched dispatch.

One :class:`ServingEngine` owns one estimator for the lifetime of the
service.  At construction the model pytree is ``jax.device_put`` ONCE —
replicated over a ``make_local_mesh(data, model)`` mesh when given — and
every subsequent dispatch closes over that resident copy, so parameters
never re-transfer per tick (the PR 3 pytree property is exactly the hook:
``device_put`` preserves the identity-hashed aux, so the resident model's
treedef equals the original's and jit caches keyed on it keep hitting).

Dispatch is the ring's bucket-shaped :class:`TraceBatch` (with its rows'
segment carries) through
``model.estimate(...)``, wrapped in ``jax.jit`` and — on a multi-device
mesh — ``shard_map`` with the trace axis split over EVERY mesh axis
(``P(("data", "model"))``): per-trace estimation is embarrassingly
parallel (no cross-trace reduction anywhere in the integrator), so the
sharded result is bitwise identical to the single-device one, which the
parity suite asserts.  The vendor/module-axis half of the mesh story
lives in ``fleet.fleet_surface_energy(mesh=)``, where the module axis is
the dispatch's vendor axis and shards over ``'model'``.

A 1-device mesh (or no mesh) skips ``shard_map`` entirely.  On a
multi-device mesh every dispatch is sharded: a batch whose trace count
does not divide the device count pads with zero-weight rows (exact by the
TraceBatch contract) that are sliced off the result.

The compiled-program cache is keyed on (vendors, mode/impl are fixed per
engine, sharded-or-not); with ring bucketing bounding the batch shapes,
``cache_size()`` is bounded by ``len(count_buckets) * len(length_buckets)``
per key — the dispatch auditor's serving probe holds this.
"""
from __future__ import annotations

import math

import jax

from repro.core import model_api
from repro.core.energy_model import gather_free
from repro.core.estimate_batch import TraceBatch
from repro.core.fleet import pad_rows
from repro.kernels.common import pad_carry
from repro.runtime.spans import span


class ServingEngine:
    """Resident-model dispatcher over an optional ``(data, model)`` mesh.

    ``mode``/``impl``/fractions are fixed per engine (a service serves ONE
    estimation configuration); ``vendors`` varies per dispatch (vendor-
    subset requests are grouped by the ring)."""

    def __init__(self, model, *, mesh=None, impl: str = "vectorized",
                 mode: str = "mean", data=None, ones_frac=None,
                 toggle_frac=None):
        self.data = model_api.normalize_data_profile(data, ones_frac,
                                                     toggle_frac)
        model_api.validate_data_profile(mode, self.data)
        self.impl = model_api.resolve_impl(impl, mode=mode).name
        self.mode = mode
        self.ones_frac = self.data.ones_frac
        self.toggle_frac = self.data.toggle_frac
        self.mesh = mesh
        self.n_shards = (math.prod(mesh.shape.values())
                         if mesh is not None else 1)
        # serving shard_maps the TRACE axis, so the model rides replicated
        # (axis=None); the module-axis twin — stacked fleet params sharded
        # over 'model' — lives in fleet.FleetStackCache
        self.resident = model_api.device_resident(model, mesh, axis=None)
        self._fns: dict[tuple, object] = {}

    # ------------------------------------------------------------ dispatch
    def dispatch(self, tb: TraceBatch, vendors=None):
        """Score one bucket-shaped batch -> the model's report (leaves
        (traces, vendors)-shaped; mode='range' a (lo, mean, hi) triple).
        Shards the trace axis when the mesh has >1 device, padding it to
        a multiple of the device count; identical numerics either way.
        The ``engine.dispatch`` span times the program lookup and the
        enqueue, not the device work; its ``gather_free`` is 1 where the
        bucket's row length reads earlier commands without gathers
        (``energy_model.gather_free``)."""
        vendors = (tuple(int(v) for v in vendors)
                   if vendors is not None else None)
        with span("engine.dispatch") as s:
            s.attrs["gather_free"] = int(gather_free(tb.trace.cmd.shape[1]))
            if self.n_shards == 1:
                return self._dispatch_fn(vendors, False)(
                    self.resident, tb.trace, tb.weight, tb.carry)
            trace, weight = pad_rows(tb.trace, tb.weight, self.n_shards)
            carry = pad_carry(tb.carry, weight.shape[0])
            out = self._dispatch_fn(vendors, True)(self.resident, trace,
                                                   weight, carry)
            return jax.tree_util.tree_map(lambda x: x[:tb.n_traces], out)

    def _dispatch_fn(self, vendors, sharded: bool):
        # The model rides as a traced ARGUMENT, not a closure: the jit
        # cache keys on its treedef (identity-hashed aux), so a treedef-
        # stable parameter update (see update_model) re-uses every
        # compiled program instead of recompiling the world.
        fn = self._fns.get((vendors, sharded))
        if fn is None:
            def serve_estimate(m, trace, weight, carry):
                return m.estimate(
                    TraceBatch(trace, weight, carry), vendors, mode=self.mode,
                    impl=self.impl, ones_frac=self.ones_frac,
                    toggle_frac=self.toggle_frac)

            if sharded:
                from jax.sharding import PartitionSpec as P
                spec = P(tuple(self.mesh.axis_names))
                serve_estimate = jax.shard_map(
                    serve_estimate, mesh=self.mesh,
                    in_specs=(P(), spec, spec, spec), out_specs=spec,
                    check_vma=False)
            fn = jax.jit(serve_estimate)
            self._fns[(vendors, sharded)] = fn
        return fn

    # ----------------------------------------------------------- lifecycle
    def cache_size(self) -> int:
        """Total compiled programs across every dispatch function — the
        quantity the serving recompile probe bounds."""
        return sum(fn._cache_size() for fn in self._fns.values())

    def update_model(self, model) -> None:
        """Swap in updated parameters (the online-recalibration hook:
        fit-while-serving pushes refreshed fits here between ticks).

        Treedef-stable updates — derived from the engine's current model,
        e.g. ``tree_map`` over ``self.resident``, which preserves the
        identity-hashed aux — re-use every compiled program (the model is
        a traced argument, so the jit cache keys on its treedef).  A
        structurally new model works too, at the cost of a recompile."""
        self.resident = model_api.device_resident(model, self.mesh)
