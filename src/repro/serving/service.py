"""Admission + metrics layer: the estimation service itself.

:class:`EstimationService` glues the serving stack together —

    submit / submit_many           (lint gate -> ring admission)
        -> TraceRing               (bucketed re-padding, FIFO windows)
        -> ServingEngine.dispatch  (resident model, sharded jit)
        -> per-ticket result rows  (+ latency / throughput counters)

Admission routes every ingested trace through the ``trace_lint`` JEDEC
gate: a protocol-illegal trace is returned as a structured
:class:`Rejection` (rule id, command index, bank — the linter's
diagnostics verbatim), never silently priced, and never blocks the legal
traces admitted alongside it.  A trace longer than a whole ring window
(largest count bucket x largest length bucket) rejects the same way
(reason ``'too-long'``).  A trace past the largest length bucket is cut
into rows at admission (``ring.split``): the lint and the ring read the
same cut, each row with the carry of the commands before it, and the
ticket's rows are summed into one report before it is returned.

Dispatch happens on :meth:`step` (one ring window), :meth:`maybe_step`
(cadence-gated, for an ingestion loop's hot path), or :meth:`drain`
(flush everything — shutdown).  Results are keyed by ticket: each
admitted trace's rows of the batched report matrix, combined and sliced
out after the dispatch completes.

:meth:`metrics` snapshots the per-dispatch counters the ROADMAP's
serving item asks for: queue depth, batch fill, sustained traces/s,
p50/p99 submit-to-result latency, rejection counts by rule, and the
engine's compiled-program count (the quantity the recompile probe
bounds).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Sequence

import jax
import numpy as np

from repro.core.dram import TCK_NS, VDD, CommandTrace
from repro.core.energy_model import EnergyReport
from repro.runtime.spans import span
from repro.serving.engine import ServingEngine
from repro.serving.ring import (RingConfig, TraceRing, TraceTooLongError,
                                split)

#: completions (dispatches) the latency (dispatch-time and fill) samples
#: keep, and rejections :attr:`EstimationService.rejections` keeps
RECENT = 4096


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Knobs of one service instance (one estimation configuration).

    ``data`` is the typed :class:`~repro.core.model_api.DataProfile`
    spelling of the data-dependence fractions; the loose
    ``ones_frac``/``toggle_frac`` fields remain accepted and both
    spellings meet in the engine's ``normalize_data_profile`` call."""
    ring: RingConfig = RingConfig()
    mode: str = "mean"
    impl: str = "vectorized"
    lint: bool = True            # the ingestion gate; off only for trusted
    cadence_s: float = 0.0       # maybe_step dispatch period (0 = every call)
    max_batch: int | None = None   # per-window cap (<= ring max_batch)
    data: object | None = None     # model_api.DataProfile
    ones_frac: float | None = None
    toggle_frac: float | None = None


@dataclasses.dataclass(frozen=True)
class Rejection:
    """One refused submission, with the evidence."""
    ticket: int
    reason: str                  # 'protocol' | 'too-long'
    diagnostics: tuple           # linter Diagnostics ('protocol' only)

    @property
    def rules(self) -> tuple[str, ...]:
        if self.reason != "protocol":
            return (self.reason,)
        return tuple(sorted({d.rule for d in self.diagnostics}))


@dataclasses.dataclass(frozen=True)
class MetricsSnapshot:
    """Counters since service construction (one dispatch granularity).
    ``batch_fill`` and the percentiles are over the last :data:`RECENT`
    dispatches and completions."""
    admitted: int
    rejected: int
    rejected_by_rule: dict[str, int]
    dispatches: int
    dispatched_traces: int
    completed: int
    queue_depth: int
    batch_fill: float            # mean real-slots / padded-slots
    traces_per_s: float          # completed / (last completion - first admit)
    latency_p50_ms: float        # submit -> result available (sliced)
    latency_p99_ms: float
    dispatch_p50_ms: float       # one engine dispatch, block_until_ready
    dispatch_p99_ms: float
    engine_programs: int         # compiled-program count (bounded by ring)
    # online-recalibration telemetry (zeros unless a fitter is attached)
    drift_score: float = 0.0     # last observe_telemetry's detector score
    drift_peak: float = 0.0      # max score seen since construction
    drift_by_key: dict[str, float] = dataclasses.field(default_factory=dict)
    recalibrations: int = 0      # refits pushed through update_model


def combine_rows(rep, rows: Sequence[int]):
    """A window's host report (leaves with a leading row axis; a
    ``(lo, mean, hi)`` triple in range mode) -> one entry per ticket,
    ticket ``i`` owning ``rows[i]`` consecutive rows.  The charge and the
    cycles are summed over a ticket's rows (per cell in surface mode; the
    cycles in int64, since a whole trace may pass 2^31); the current,
    energy and time are derived from those sums, never summed.  A one-row
    ticket keeps its row as it is."""
    if isinstance(rep, tuple) and not isinstance(rep, EnergyReport):
        return tuple(combine_rows(r, rows) for r in rep)
    rows = np.asarray(rows)
    if (rows == 1).all():
        return rep
    starts = np.concatenate([[0], np.cumsum(rows)[:-1]])
    charge = np.add.reduceat(rep.charge_ma_cycles.astype(np.float64),
                             starts, axis=0)
    cycles = np.add.reduceat(rep.cycles.astype(np.int64), starts, axis=0)
    whole = {"charge_ma_cycles": charge, "cycles": cycles,
             "avg_current_ma": charge / np.maximum(cycles, 1),
             "energy_pj": charge * TCK_NS * VDD,
             "time_ns": cycles * TCK_NS}
    one = (rows == 1).reshape((-1,) + (1,) * (charge.ndim - 1))
    return EnergyReport(**{
        f: np.where(one, getattr(rep, f)[starts], whole[f]).astype(
            np.int64 if f == "cycles" else getattr(rep, f).dtype)
        for f in EnergyReport._fields})


def _windows(items: Sequence[int], rows: Sequence[int], limit: int
             ) -> list[list[int]]:
    """``items`` in order, cut into runs whose ``rows`` sum to at most
    ``limit`` (each item's rows are at most ``limit``)."""
    out: list[list[int]] = []
    room = 0
    for item, n in zip(items, rows):
        if n > room:
            out.append([])
            room = limit
        out[-1].append(item)
        room -= n
    return out


def _pct(samples: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(samples), q) * 1e3) \
        if samples else 0.0


class EstimationService:
    """The continuously batched estimation front end (single process:
    the concurrency is in the batched dispatch, not in threads)."""

    def __init__(self, model=None, config: ServiceConfig | None = None, *,
                 mesh=None, engine: ServingEngine | None = None,
                 fitter=None):
        self.config = config or ServiceConfig()
        self.ring = TraceRing(self.config.ring)
        # a prebuilt engine carries its resident model AND its compiled
        # programs into the new service (fresh counters, warm jit cache)
        self.engine = engine if engine is not None else ServingEngine(
            model, mesh=mesh, impl=self.config.impl, mode=self.config.mode,
            data=self.config.data,
            ones_frac=self.config.ones_frac,
            toggle_frac=self.config.toggle_frac)
        # optional streaming fitter (repro.core.recalibrate.StreamingFitter):
        # telemetry flows in through observe_telemetry, refreshed fits flow
        # out through engine.update_model — fit-while-serving
        self.fitter = fitter
        self._drift_last: object | None = None
        self._drift_peak = 0.0
        self._recalibrations = 0
        self._results: dict[int, object] = {}
        self._submit_t: dict[int, float] = {}
        self._next_ticket = 0
        self._closed = False
        self._last_dispatch_t = 0.0
        # counters
        self._admitted = 0
        self._rejected_by_rule: dict[str, int] = {}
        self._rejected = 0
        self._rejections: collections.deque = collections.deque(
            maxlen=RECENT)
        self._dispatches = 0
        self._dispatched = 0
        self._completed = 0
        self._first_admit_t: float | None = None
        self._last_done_t = 0.0
        self._fills: collections.deque = collections.deque(maxlen=RECENT)
        self._dispatch_s: collections.deque = collections.deque(
            maxlen=RECENT)
        self._latency_s: collections.deque = collections.deque(
            maxlen=RECENT)

    # ----------------------------------------------------------- admission
    def submit(self, trace: CommandTrace,
               vendors: Sequence[int] | None = None) -> int | Rejection:
        """Admit one trace.  Returns its ticket, or a :class:`Rejection`
        when the lint gate (or the ring's length cap) refuses it."""
        tickets, rejections = self.submit_many([trace], vendors)
        return rejections[0] if rejections else tickets[0]

    def submit_many(self, traces: Sequence[CommandTrace],
                    vendors: Sequence[int] | None = None
                    ) -> tuple[list[int | None], list[Rejection]]:
        """Admit a burst: each trace cut into ring rows with their
        carries, ONE batched lint over the whole burst's rows, then
        per-trace admission.  Illegal traces become
        :class:`Rejection`\\ s (their slot in ``tickets`` is ``None``);
        the legal ones are admitted regardless — a mixed burst never
        blocks its clean members.  ``vendors`` scopes the whole burst
        (the ring groups windows by vendor subset).

        The lint runs at most one window of rows at a time (its rows
        padded to a power of two), so the shapes it compiles are bounded
        as the ring's are.  Span ``trace.segment``: the cut and the carry scan
        (counts ``segments``, the rows made, and ``long_traces``, the
        traces that needed more than one)."""
        if self._closed:
            raise RuntimeError("service is closed")
        from repro.analysis import trace_lint
        ring = self.ring.config
        traces = list(traces)
        with span("trace.segment") as s:
            segs = [split(tr, ring.max_length)
                    if int(tr.n) <= ring.max_commands else None
                    for tr in traces]
            kept = [i for i, g in enumerate(segs) if g is not None]
            s.attrs["segments"] = sum(segs[i].rows for i in kept)
            s.attrs["long_traces"] = sum(segs[i].rows > 1 for i in kept)
        errors_by_trace: dict[int, list] = {}
        if self.config.lint:
            for chunk in _windows(kept, [segs[i].rows for i in kept],
                                  ring.max_batch):
                for d in trace_lint.errors_of(trace_lint.lint_traces(
                        [segs[i] for i in chunk])):
                    errors_by_trace.setdefault(chunk[d.trace_index],
                                               []).append(d)
        group = (tuple(int(v) for v in vendors)
                 if vendors is not None else None)
        tickets: list[int | None] = []
        rejections: list[Rejection] = []
        now = time.perf_counter()
        for i, tr in enumerate(traces):
            ticket = self._next_ticket
            self._next_ticket += 1
            diags = errors_by_trace.get(i)
            if diags:
                rejections.append(self._reject(
                    Rejection(ticket, "protocol", tuple(diags))))
                tickets.append(None)
                continue
            try:
                self.ring.admit(tr if segs[i] is None else segs[i],
                                ticket=ticket, group=group)
            except TraceTooLongError:
                rejections.append(self._reject(
                    Rejection(ticket, "too-long", ())))
                tickets.append(None)
                continue
            self._submit_t[ticket] = now
            if self._first_admit_t is None:
                self._first_admit_t = now
            self._admitted += 1
            tickets.append(ticket)
        return tickets, rejections

    def _reject(self, r: Rejection) -> Rejection:
        self._rejected += 1
        self._rejections.append(r)
        for rule in r.rules:
            self._rejected_by_rule[rule] = \
                self._rejected_by_rule.get(rule, 0) + 1
        return r

    # ------------------------------------------------------------ dispatch
    def step(self) -> int:
        """Dispatch ONE ring window; returns how many real traces it
        scored (0 on an empty ring — the empty flush is a no-op).

        Spans (besides the ring's and the engine's): ``engine.block``,
        the wait for the device, and ``service.slice``, the report to the
        host and its per-ticket rows (``bytes`` fetched), around
        ``service.combine``, the rows summed per ticket (``rows``)."""
        rb = self.ring.take(self.config.max_batch)
        if rb is None:
            return 0
        t0 = time.perf_counter()
        rep = self.engine.dispatch(rb.batch, rb.group)
        with span("engine.block"):
            jax.block_until_ready(rep)
        t1 = time.perf_counter()
        self._last_dispatch_t = t1
        self._dispatches += 1
        self._dispatched += rb.n_real
        self._fills.append(rb.fill)
        self._dispatch_s.append(t1 - t0)
        with span("service.slice") as s:
            s.attrs["bytes"] = sum(x.nbytes
                                   for x in jax.tree_util.tree_leaves(rep))
            rep = jax.tree_util.tree_map(np.asarray, rep)
            with span("service.combine") as c:
                rep = combine_rows(rep, rb.rows)
                c.attrs["rows"] = sum(rb.rows)
            for i, ticket in enumerate(rb.tickets):
                self._results[ticket] = jax.tree_util.tree_map(
                    lambda x: x[i], rep)
                done = time.perf_counter()
                self._latency_s.append(done - self._submit_t.pop(ticket, t0))
                self._completed += 1
        self._last_done_t = done
        return rb.n_real

    def maybe_step(self) -> int:
        """The ingestion loop's hot-path tick: dispatch only when the
        cadence period has elapsed (and the ring is non-empty)."""
        if not len(self.ring):
            return 0
        if time.perf_counter() - self._last_dispatch_t < self.config.cadence_s:
            return 0
        return self.step()

    def drain(self) -> int:
        """Flush every pending window (shutdown / end-of-burst); returns
        the total real traces dispatched."""
        total = 0
        while True:
            n = self.step()
            if n == 0:
                return total
            total += n

    def close(self) -> int:
        """Drain, then refuse further submissions."""
        n = self.drain()
        self._closed = True
        return n

    # ----------------------------------------------------------- telemetry
    def observe_telemetry(self, currents, cell_idx, tick: int):
        """Feed one tick of fleet telemetry to the attached streaming
        fitter; when its drift detector fires, refit from the accumulated
        sufficient statistics and hot-swap the refreshed parameters into
        the engine (treedef-stable, so no dispatch recompiles).  Returns
        the fitter's :class:`~repro.core.recalibrate.DriftReport`."""
        if self.fitter is None:
            raise RuntimeError(
                "no streaming fitter attached; construct the service with "
                "fitter=model_api.fit(fitter='streaming', ...)")
        report = self.fitter.observe(currents, cell_idx, tick)
        self._drift_last = report
        self._drift_peak = max(self._drift_peak, report.score)
        if report.triggered:
            self.engine.update_model(self.fitter.refit())
            self._recalibrations += 1
        return report

    # ------------------------------------------------------------- results
    def result(self, ticket: int):
        """Pop one completed ticket's report row (leaves vendor-shaped;
        ``mode='range'`` a (lo, mean, hi) triple of rows).  Raises
        ``KeyError`` while the ticket is still queued."""
        if ticket not in self._results and ticket in self._submit_t:
            raise KeyError(f"ticket {ticket} not yet dispatched "
                           f"(queue depth {len(self.ring)}; call step/drain)")
        return self._results.pop(ticket)

    @property
    def rejections(self) -> tuple[Rejection, ...]:
        """The last :data:`RECENT` rejections, oldest first."""
        return tuple(self._rejections)

    # ------------------------------------------------------------- metrics
    def metrics(self) -> MetricsSnapshot:
        wall = (self._last_done_t - self._first_admit_t
                if self._first_admit_t is not None else 0.0)
        return MetricsSnapshot(
            admitted=self._admitted,
            rejected=self._rejected,
            rejected_by_rule=dict(self._rejected_by_rule),
            dispatches=self._dispatches,
            dispatched_traces=self._dispatched,
            completed=self._completed,
            queue_depth=len(self.ring),
            batch_fill=float(np.mean(self._fills)) if self._fills else 0.0,
            traces_per_s=self._completed / wall if wall > 0 else 0.0,
            latency_p50_ms=_pct(self._latency_s, 50),
            latency_p99_ms=_pct(self._latency_s, 99),
            dispatch_p50_ms=_pct(self._dispatch_s, 50),
            dispatch_p99_ms=_pct(self._dispatch_s, 99),
            engine_programs=self.engine.cache_size(),
            drift_score=(self._drift_last.score
                         if self._drift_last is not None else 0.0),
            drift_peak=self._drift_peak,
            drift_by_key=(dict(self._drift_last.by_key)
                          if self._drift_last is not None else {}),
            recalibrations=self._recalibrations)
