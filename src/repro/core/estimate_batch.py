"""Batched multi-trace estimation engine (the consumer-side twin of
``repro.core.fleet``).

``fleet`` collapsed the *characterization* campaign into vmapped dispatches;
this module does the same for a fitted model's *estimation* path, which is
where every downstream study (encodings, validation, serving) spends its
time once a model exists. One (trace, vendor) pair per Python call is one
separately-dispatched, separately-compiled JAX program per trace length;
here the whole (traces x vendors) energy-report matrix is a single jitted
``vmap(vmap(...))`` over the shared integrator:

* heterogeneous :class:`CommandTrace` lengths are NOP/dt=0-padded into one
  fixed-shape :class:`TraceBatch` (``dram.batch_traces`` — a zero-cycle NOP
  draws no charge and perturbs no integrator state, so padding is exact);
* :func:`batched_reports` evaluates every (trace, paramset) pair in one
  dispatch and returns an :class:`EnergyReport` whose leaves have shape
  ``(traces, vendors)``;
* :func:`batched_range_reports` additionally vmaps the per-vendor process-
  variation band -> (lo, mean, hi) report matrices;
* :func:`batched_distribution_reports` is the paper's no-data-trace mode
  (caller-supplied ones/toggle fractions) over the same batch;
* :func:`batched_surface_reports` is the structural-variation surface mode
  (paper Figs 19-22): the same integrator grouped per (bank, row-band)
  cell -> ``(traces, vendors, banks, row_bands)``-shaped report leaves,
  the whole fleet in one dispatch;
* the ``pallas_*`` twins evaluate the identical contracts through the
  fused Pallas kernel family (``impl='pallas'`` in the registry): the
  param-independent feature kernel once per batch, the per-vendor energy
  kernel gridded over the vendor axis.

This module holds the ENGINE only.  The model-facing surface is the
unified estimator protocol (``repro.core.model_api``): every estimator's
``estimate(traces, vendors, mode=...)`` feeds these dispatches with its
own stacked parameter leaves (stacked once at fit/construction time, not
per call).  Callers scoring the same trace set repeatedly (the serving
power loop, the encoding study) should build the :class:`TraceBatch` once
and reuse it — models also memoize the padding of recently seen trace
sets (``model_api.TraceBatchCache``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dram import (CommandTrace, N_BANKS, N_ROW_BANDS, batch_traces,
                             stack_padded)
from repro.core.energy_model import (EnergyReport, PowerParams,
                                     SegmentCarry, _report,
                                     charge_from_features,
                                     distribution_features,
                                     extract_structural_features,
                                     finalize_features, scale_report,
                                     surface_charge, surface_cycles)
from repro.core.fleet import batched_pair_totals, pad_leading, pad_rows


@dataclasses.dataclass(frozen=True)
class TraceBatch:
    """A fixed-shape batch of command traces (leading trace axis on every
    field) plus the validity mask that excludes padding slots.  ``carry``
    (optional, a leading row axis on every leaf) makes each row a segment
    of a longer trace: the state its commands inherit
    (``energy_model.SegmentCarry``); None is the empty carry."""
    trace: CommandTrace   # (T, N) on every field
    weight: jax.Array     # (T, N) float32: 1 for real commands, 0 for pad
    carry: SegmentCarry | None = None

    @classmethod
    def from_traces(cls, traces: Sequence[CommandTrace]) -> "TraceBatch":
        batch, weight = batch_traces([(tr, 0) for tr in traces])
        return cls(batch, weight)

    @property
    def n_traces(self) -> int:
        return self.trace.cmd.shape[0]


def as_trace_batch(traces) -> TraceBatch:
    """Accept a prebuilt :class:`TraceBatch`, a single trace, or a sequence
    of (ragged) traces."""
    if isinstance(traces, TraceBatch):
        return traces
    if isinstance(traces, CommandTrace):
        traces = [traces]
    return TraceBatch.from_traces(list(traces))


def bucketed_trace_batch(traces: Sequence[CommandTrace], n_slots: int,
                         length: int) -> TraceBatch:
    """Pad ragged traces into a FIXED ``(n_slots, length)`` batch shape.

    ``TraceBatch.from_traces`` pads to the request's own max length/count,
    so every distinct request shape is a fresh compile of the batched
    dispatches; this builder instead targets a caller-chosen bucket shape
    (the serving ring's vocabulary): the command axis NOP/dt=0-pads to
    ``length`` and whole zero-weight pad rows fill the trace axis up to
    ``n_slots``.  Both paddings are exact — pad commands draw no charge
    and move no state, pad rows contribute neither charge nor cycles."""
    if not traces:
        raise ValueError("bucketed_trace_batch needs at least one trace")
    if len(traces) > n_slots:
        raise ValueError(f"{len(traces)} traces exceed {n_slots} slots")
    longest = max(int(tr.n) for tr in traces)
    if longest > length:
        raise ValueError(f"longest trace ({longest} commands) exceeds the "
                         f"length bucket ({length})")
    weight = np.zeros((n_slots, length), np.float32)
    for i, tr in enumerate(traces):
        weight[i, :int(tr.n)] = 1.0
    return TraceBatch(stack_padded(traces, length, n_slots),
                      jnp.asarray(weight))


def original_traces(traces, tb: TraceBatch) -> list[CommandTrace]:
    """The caller's ragged traces when recoverable from the ``estimate``
    argument, else the padded batch rows — exact either way (a dt=0 NOP
    draws no charge and moves no integrator state).  Shared by every
    pair-at-a-time ``impl='reference'`` oracle."""
    if isinstance(traces, CommandTrace):
        return [traces]
    if isinstance(traces, (list, tuple)):
        return list(traces)
    return [jax.tree_util.tree_map(lambda x: x[i], tb.trace)
            for i in range(tb.n_traces)]


def row_carries(tb: TraceBatch) -> list:
    """Each row's segment carry (None for every row of an uncarried
    batch), for the pair-at-a-time ``impl='reference'`` oracles."""
    if tb.carry is None:
        return [None] * tb.n_traces
    return [jax.tree_util.tree_map(lambda x: x[i], tb.carry)
            for i in range(tb.n_traces)]


# ---------------------------------------------------------------------------
# The batched dispatches
# ---------------------------------------------------------------------------
@jax.jit
def batched_reports(trace: CommandTrace, weight: jax.Array,
                    stacked: PowerParams,
                    carry: SegmentCarry | None = None) -> EnergyReport:
    """Energy reports of every (trace, vendor) pair in one dispatch.

    ``trace``/``weight`` are a TraceBatch's padded fields; ``stacked`` is
    ``stack_params`` over the fitted vendor params; ``carry`` the rows'
    segment carries (None: whole traces). Returns an EnergyReport
    whose every leaf has shape (traces, vendors); the charge/cycle core is
    ``fleet.batched_pair_totals``, shared with the campaign engine."""
    def one_trace(tr: CommandTrace, w: jax.Array, c):
        return batched_pair_totals(
            tr, w, extract_structural_features(tr, carry=c), stacked)

    charge, cycles = jax.vmap(one_trace)(trace, weight, carry)  # (T, V), (T,)
    return _report(charge, jnp.broadcast_to(cycles[:, None], charge.shape))


@jax.jit
def batched_range_reports(trace: CommandTrace, weight: jax.Array,
                          stacked: PowerParams, band: jax.Array,
                          carry: SegmentCarry | None = None
                          ) -> tuple[EnergyReport, EnergyReport, EnergyReport]:
    """(lo, mean, hi) report matrices across the per-vendor process-variation
    band. ``band`` is a float32 (vendors, 2) array of multiplicative
    (lo, hi) factors, broadcast over the (traces, vendors) matrix inside the
    same dispatch rather than applied to a scalar current after the fact,
    so *every* report field (charge, current, energy) carries the band."""
    mean = batched_reports(trace, weight, stacked, carry)
    lo = scale_report(mean, band[None, :, 0])   # (1, V) over the trace axis
    hi = scale_report(mean, band[None, :, 1])
    return lo, mean, hi


@jax.jit
def batched_distribution_reports(trace: CommandTrace, weight: jax.Array,
                                 stacked: PowerParams, ones_frac: jax.Array,
                                 toggle_frac: jax.Array,
                                 carry: SegmentCarry | None = None
                                 ) -> EnergyReport:
    """No-data-trace mode over the batch: expected ones/toggle fractions
    replace the per-command data features (paper Section 9.2 fallback).

    ``ones_frac``/``toggle_frac`` broadcast per trace: scalars or (T,)
    arrays. First-access semantics match ``extract_features``: the first
    RD/WR on the bus has no previous burst, so its expected toggles are 0.
    """
    ones_frac = jnp.broadcast_to(jnp.asarray(ones_frac, jnp.float32),
                                 (trace.cmd.shape[0],))
    toggle_frac = jnp.broadcast_to(jnp.asarray(toggle_frac, jnp.float32),
                                   (trace.cmd.shape[0],))

    def one_trace(tr: CommandTrace, w, of, tf, c):
        sf = distribution_features(extract_structural_features(tr, carry=c),
                                   of, tf)
        return batched_pair_totals(tr, w, sf, stacked)

    charge, cycles = jax.vmap(one_trace)(trace, weight, ones_frac,
                                         toggle_frac, carry)
    return _report(charge, jnp.broadcast_to(cycles[:, None], charge.shape))


def batched_surface_reports(trace: CommandTrace, weight: jax.Array,
                            stacked: PowerParams,
                            carry: SegmentCarry | None = None
                            ) -> EnergyReport:
    """The fleet-wide structural-variation surfaces (``mode='surface'``):
    every (trace, vendor) pair's per-(bank, row-band) energy decomposition
    in ONE dispatch — no per-module Python sweeps.  Returns an
    :class:`EnergyReport` whose every leaf has shape
    ``(traces, vendors, banks, row_bands)``; summing the cell axes
    recovers :func:`batched_reports` exactly (same integrator, grouped by
    the structural cell index instead of totalled).

    The charge program is the SAME jitted chunk program the fleet-scale
    chunked dispatch runs (:func:`_surface_chunk_charge` with the whole
    module axis as one chunk), so chunked-vs-one-shot parity is bitwise
    by construction, not merely allclose."""
    charge = _surface_chunk_charge(trace, weight, stacked, False, False,
                                   carry)
    cycles = _surface_cycles_batch(trace, weight)          # (T, 8, R)
    return _report(charge, jnp.broadcast_to(cycles[:, None], charge.shape))


# ---------------------------------------------------------------------------
# Chunked surface dispatch: the fleet-scale twin of
# ``batched_surface_reports``.
#
# The one-shot surface dispatch materializes every (trace, module) pair's
# per-command intermediates at once — for a 10k-50k module fleet that is
# tens of GB of finalize/charge planes for a result that is only
# ``(T, V, 8, R)``.  The chunked path bounds live memory to ONE module
# chunk's intermediates: a Python loop over fixed-shape chunk programs
# (the loop is host-side so the compiled-program count depends on the
# chunk SIZE, never the chunk COUNT — growing the fleet reuses the same
# program, the property ``analysis.dispatch_audit.audit_fleet_chunked``
# asserts), each chunk's charge scattered into a DONATED full-width
# accumulator (``_scatter_chunk`` donates its carry, so XLA updates the
# surface in place instead of copying it per chunk).  Exact parity with
# the one-shot path: identical per-(trace, module) math, identical
# ``_report`` finalization, pad modules (chunk-size remainder) sliced off
# before the report is built.
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("pallas", "interpret"))
def _surface_chunk_charge(trace: CommandTrace, weight, chunk_pp: PowerParams,
                          pallas: bool, interpret: bool,
                          carry: SegmentCarry | None = None):
    """One module chunk's surface charge -> (T, chunk, 8, R) f32.  The
    per-pair math is verbatim :func:`batched_surface_reports` (vectorized)
    or the fused surface kernel (pallas), so chunked == one-shot holds
    leaf-exactly."""
    if pallas:
        from repro.kernels.vampire_energy import ops as vops
        charge, _ = vops.batched_charge_matrix(trace, weight, chunk_pp,
                                               surface=True,
                                               interpret=interpret,
                                               carry=carry)
        return charge

    def one_trace(tr: CommandTrace, w: jax.Array, c):
        sf = extract_structural_features(tr, carry=c)

        def one_paramset(pp: PowerParams):
            charges = charge_from_features(tr, finalize_features(sf, pp), pp)
            return surface_charge(tr, w, charges)          # (8, R)

        return jax.vmap(one_paramset)(chunk_pp)            # (chunk, 8, R)

    return jax.vmap(one_trace)(trace, weight, carry)       # (T, chunk, 8, R)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_chunk(acc, charge, t_start, m_start):
    """Write one chunk's (t, c, 8, R) charge into the full surface at the
    (trace, module) offset (traced i32 scalars, so every chunk index
    reuses one compiled program).  ``acc`` is donated: the accumulator is
    updated in place across the chunk loop, never copied."""
    zero = jnp.int32(0)
    return jax.lax.dynamic_update_slice(
        acc, charge, (jnp.asarray(t_start, jnp.int32),
                      jnp.asarray(m_start, jnp.int32), zero, zero))


@jax.jit
def _surface_cycles_batch(trace: CommandTrace, weight) -> jax.Array:
    return jax.vmap(surface_cycles)(trace, weight)         # (T, 8, R)


def chunked_surface_reports(trace: CommandTrace, weight, stacked: PowerParams,
                            *, module_chunk: int,
                            trace_chunk: int | None = None,
                            impl: str = "vectorized",
                            interpret: bool | None = None) -> EnergyReport:
    """Memory-bounded ``mode='surface'`` over a stacked module axis of any
    size: :func:`batched_surface_reports`' exact result, evaluated
    ``module_chunk`` modules (and optionally ``trace_chunk`` traces) at a
    time.  ``impl`` is ``'vectorized'`` or ``'pallas'``."""
    from repro.kernels.common import interpret_default
    pallas = impl == "pallas"
    if interpret is None:
        interpret = interpret_default()
    # interpret only steers the pallas lowering; pin it on the vectorized
    # path so both the one-shot and chunked dispatch share ONE jit entry
    interpret = bool(interpret) if pallas else False
    n_modules = stacked.i2n.shape[0]
    n_traces = trace.cmd.shape[0]
    module_chunk = min(int(module_chunk), n_modules)
    trace_chunk = (n_traces if trace_chunk is None
                   else min(int(trace_chunk), n_traces))

    m_pad = (-n_modules) % module_chunk
    stacked = pad_leading(stacked, m_pad)
    cycles = _surface_cycles_batch(trace, weight)
    trace, weight = pad_rows(trace, weight, trace_chunk)
    t_padded = trace.cmd.shape[0]

    acc = jnp.zeros((t_padded, n_modules + m_pad, N_BANKS, N_ROW_BANDS),
                    jnp.float32)
    for ti in range(0, t_padded, trace_chunk):
        tr_c = jax.tree_util.tree_map(lambda x: x[ti:ti + trace_chunk],
                                      trace)
        w_c = weight[ti:ti + trace_chunk]
        for mi in range(0, n_modules + m_pad, module_chunk):
            chunk_pp = jax.tree_util.tree_map(
                lambda x: x[mi:mi + module_chunk], stacked)
            charge = _surface_chunk_charge(tr_c, w_c, chunk_pp, pallas,
                                           interpret)
            acc = _scatter_chunk(acc, charge, jnp.int32(ti), jnp.int32(mi))
    charge = acc[:n_traces, :n_modules]
    return _report(charge, jnp.broadcast_to(cycles[:, None], charge.shape))


# ---------------------------------------------------------------------------
# The fused Pallas dispatches (impl='pallas'): same contracts as the
# vectorized trio above, evaluated by the batched kernel family in
# ``repro.kernels.vampire_energy`` (feature kernel once per batch, energy
# kernel gridded over the vendor axis).  Interpret-vs-compiled resolves per
# call inside ``ops.batched_charge_matrix``.
# ---------------------------------------------------------------------------
def pallas_batched_reports(trace: CommandTrace, weight: jax.Array,
                           stacked: PowerParams,
                           carry: SegmentCarry | None = None
                           ) -> EnergyReport:
    """impl='pallas' twin of :func:`batched_reports`."""
    from repro.kernels.vampire_energy import ops as vops
    charge, cycles = vops.batched_charge_matrix(trace, weight, stacked,
                                                carry=carry)
    return _report(charge, jnp.broadcast_to(cycles[:, None], charge.shape))


def pallas_batched_range_reports(trace: CommandTrace, weight: jax.Array,
                                 stacked: PowerParams, band: jax.Array,
                                 carry: SegmentCarry | None = None
                                 ) -> tuple[EnergyReport, EnergyReport,
                                            EnergyReport]:
    """impl='pallas' twin of :func:`batched_range_reports`."""
    mean = pallas_batched_reports(trace, weight, stacked, carry)
    lo = scale_report(mean, band[None, :, 0])
    hi = scale_report(mean, band[None, :, 1])
    return lo, mean, hi


def pallas_batched_distribution_reports(trace: CommandTrace,
                                        weight: jax.Array,
                                        stacked: PowerParams,
                                        ones_frac: jax.Array,
                                        toggle_frac: jax.Array,
                                        carry: SegmentCarry | None = None
                                        ) -> EnergyReport:
    """impl='pallas' twin of :func:`batched_distribution_reports` (the
    feature kernel is skipped; expected fractions feed the energy kernel
    directly — scalar or per-trace, normalized by the kernel assembler —
    with first-access toggles pinned to 0)."""
    from repro.kernels.vampire_energy import ops as vops
    charge, cycles = vops.batched_charge_matrix(
        trace, weight, stacked, ones_frac=ones_frac, toggle_frac=toggle_frac,
        carry=carry)
    return _report(charge, jnp.broadcast_to(cycles[:, None], charge.shape))


def pallas_batched_surface_reports(trace: CommandTrace, weight: jax.Array,
                                   stacked: PowerParams,
                                   carry: SegmentCarry | None = None
                                   ) -> EnergyReport:
    """impl='pallas' twin of :func:`batched_surface_reports`: the energy
    kernel swaps its scalar charge sum for an in-kernel cell reduction over
    the (bank, row-band) one-hot plane, same (vendors, traces, blocks)
    grid."""
    from repro.kernels.vampire_energy import ops as vops
    charge, cycles = vops.batched_charge_matrix(trace, weight, stacked,
                                                surface=True, carry=carry)
    return _report(charge,
                   jnp.broadcast_to(cycles[:, None], charge.shape))
