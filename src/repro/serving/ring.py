"""Persistent TraceBatch ring: continuous admission + bucketed re-padding.

A serving loop cannot afford one compiled program per request shape: ragged
traces arrive continuously, and every distinct padded ``(count, length)``
shape of the batched dispatchers is a separate XLA compile.  The ring is
the fix — it admits ragged :class:`~repro.core.dram.CommandTrace`\\ s as
they arrive and, on each dispatch tick, re-pads the pending window *in
place* (persistent host-side buffers, one per bucket shape) into a small
FIXED set of pad shapes:

* the command axis rounds up to the next **length bucket**
  (:attr:`RingConfig.length_buckets`);
* the trace axis rounds up to the next **count bucket**
  (:attr:`RingConfig.count_buckets`) with all-NOP/dt=0 rows of zero
  weight.

Both paddings are exact by the repo-wide padding contract (a zero-cycle
NOP draws no charge and moves no integrator state; a zero-weight row
contributes neither charge nor cycles), so bucketed results equal the
exact-shape pad bit for bit — and the jit cache of every downstream
dispatcher is bounded by ``len(count_buckets) * len(length_buckets)``
programs no matter what traffic arrives (the dispatch auditor's
serving-path recompile probe holds this).

Count buckets are multiples of 8 so a padded batch always divides the
multi-device meshes the engine shards over (2/4/8-way ``data*model``).

A trace longer than the largest length bucket is admitted up to
``count_buckets[-1] x length_buckets[-1]`` commands: :func:`split` cuts
it into rows of at most the largest length bucket, each row with the
carries of the commands before it (the integrator's
``energy_model.SegmentCarry`` and the lint's), so the rows are
independent rows of one window and their reports sum to the whole
trace's.  A ticket's rows are
never split across windows.  A trace within the largest bucket is one
row with the empty carry.

The ring is dispatch-cadence infrastructure only: it never lints, never
estimates, and keeps no results — that is :mod:`repro.serving.service`.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Sequence

import jax
import numpy as np

from repro.analysis import trace_lint
from repro.core.dram import LINE_WORDS, CommandTrace
from repro.core.energy_model import (SegmentCarry, empty_carry,
                                     events_before, segment_carry)
from repro.core.estimate_batch import TraceBatch
from repro.runtime.spans import span


@dataclasses.dataclass(frozen=True)
class RingConfig:
    """The fixed pad-shape vocabulary (ascending, final entries = caps)."""
    length_buckets: tuple[int, ...] = (256, 1024, 4096, 16384)
    count_buckets: tuple[int, ...] = (8, 16, 32, 64)

    def __post_init__(self):
        for name in ("length_buckets", "count_buckets"):
            buckets = getattr(self, name)
            if not buckets or list(buckets) != sorted(set(buckets)):
                raise ValueError(f"{name} must be non-empty, ascending, "
                                 f"unique; got {buckets}")

    @property
    def max_batch(self) -> int:
        return self.count_buckets[-1]

    @property
    def max_length(self) -> int:
        return self.length_buckets[-1]

    @property
    def max_commands(self) -> int:
        """The longest admissible trace: one whole window of rows."""
        return self.max_batch * self.max_length


class TraceTooLongError(ValueError):
    """An admitted trace exceeds a whole window of rows (largest count
    bucket x largest length bucket) — it can never fit one window, so
    admission rejects it up front."""

    def __init__(self, n: int, limit: int):
        self.n = int(n)
        self.limit = int(limit)
        super().__init__(
            f"trace of {self.n} commands exceeds the ring's largest window "
            f"({self.limit} commands); cut it into shorter traces or "
            f"configure larger buckets")


#: the carries of a one-row trace (read only)
_EMPTY = empty_carry(1)
_EMPTY_LINT = trace_lint.empty_carry()[None]


@dataclasses.dataclass(frozen=True)
class Segments:
    """One trace as ring rows: row ``r`` is commands
    ``bounds[r]:bounds[r + 1]`` of ``trace`` (host arrays), with the
    integrator's segment carry ``carry`` and the lint's ``lint_carry``
    (each with a leading row axis)."""
    trace: CommandTrace
    bounds: np.ndarray
    carry: SegmentCarry
    lint_carry: np.ndarray

    @property
    def rows(self) -> int:
        return len(self.bounds) - 1

    @property
    def n(self) -> int:
        return int(self.bounds[-1])


def segment(trace: CommandTrace, bounds) -> Segments:
    """``trace`` cut at the ascending ``bounds`` (0 first, its length
    last), both carries of every row read from the trace alone in one
    scan (``energy_model.events_before``); one row gets the empty
    carries."""
    host = CommandTrace(*(np.asarray(x) for x in trace))
    bounds = np.asarray(bounds, np.int64)
    if len(bounds) <= 2:
        return Segments(host, bounds, _EMPTY, _EMPTY_LINT)
    ev = events_before(host, bounds[:-1])
    return Segments(host, bounds, segment_carry(host, ev),
                    trace_lint.lint_carry(host.dt, bounds[:-1], ev))


def split(trace: CommandTrace, length: int) -> Segments:
    """:func:`segment` into rows of ``length`` commands (the last one
    shorter); a trace of at most ``length`` commands is one row."""
    n = int(trace.cmd.shape[0])
    return segment(trace, np.append(np.arange(0, max(n, 1), length), n))


def bucket_for(value: int, buckets: Sequence[int]) -> int | None:
    """Smallest bucket >= ``value``, or None when the largest is exceeded."""
    for b in buckets:
        if value <= b:
            return int(b)
    return None


@dataclasses.dataclass(frozen=True)
class RingBatch:
    """One dispatch window: a bucket-shaped TraceBatch whose first
    ``sum(rows)`` rows are the real admitted traces' rows, in order
    (ticket ``tickets[i]`` owns ``rows[i]`` consecutive rows)."""
    batch: TraceBatch
    tickets: tuple[int, ...]
    group: tuple[int, ...] | None   # the vendor-subset key the entries share
    rows: tuple[int, ...]

    @property
    def n_real(self) -> int:
        return len(self.tickets)

    @property
    def slots(self) -> int:
        return self.batch.n_traces

    @property
    def fill(self) -> float:
        return sum(self.rows) / self.slots


class TraceRing:
    """FIFO admission buffer over persistent per-bucket pad buffers."""

    def __init__(self, config: RingConfig | None = None):
        self.config = config or RingConfig()
        self._pending: collections.deque = collections.deque()
        self._next_ticket = 0
        # (count_bucket, length_bucket) -> dict of reused host arrays; the
        # "re-pad in place" half of the contract: admission churn never
        # allocates fresh pad storage once a bucket shape has been seen
        self._buffers: dict[tuple[int, int], dict[str, np.ndarray]] = {}
        # count_bucket -> a window's all-empty carries, kept on the device
        self._empty_carries = {c: jax.device_put(empty_carry(c))
                               for c in self.config.count_buckets}

    def __len__(self) -> int:
        return len(self._pending)

    # ----------------------------------------------------------- admission
    def admit(self, trace: CommandTrace | Segments,
              ticket: int | None = None,
              group: tuple[int, ...] | None = None) -> int:
        """Queue one ragged trace (or its :func:`split` at the largest
        length bucket); returns its ticket.  Raises
        :class:`TraceTooLongError` when one window cannot hold it."""
        n = int(trace.n)
        if n > self.config.max_commands:
            raise TraceTooLongError(n, self.config.max_commands)
        if not isinstance(trace, Segments):
            trace = split(trace, self.config.max_length)
        if ticket is None:
            ticket = self._next_ticket
        self._next_ticket = max(self._next_ticket, ticket) + 1
        self._pending.append((int(ticket), trace, group,
                              time.perf_counter()))
        return int(ticket)

    # ------------------------------------------------------------ dispatch
    def take(self, max_batch: int | None = None) -> RingBatch | None:
        """Pop the oldest dispatch window and re-pad it into its bucket
        shape.  Whole tickets sharing the head entry's ``group``
        (vendor-subset key) are collected FIFO while their rows fit
        ``max_batch`` (the head always goes); other groups keep their
        order for later ticks.  Returns None when the ring is empty (the
        empty flush is a no-op, not an error).

        Spans: ``ring.repad`` (group pick and the host re-pad; counts
        ``n_real`` traces and their ``rows``, ``real_cmds``/``slot_cmds``
        commands and ``wait_s``, the taken traces' summed wait from
        admission to this take) and ``ring.transfer`` (the buffers to the
        device, waited for so that the whole copy lies in the span;
        ``bytes``)."""
        if not self._pending:
            return None
        with span("ring.repad") as s:
            limit = min(max_batch or self.config.max_batch,
                        self.config.max_batch)
            group = self._pending[0][2]
            picked, kept, n_rows, full = [], [], 0, False
            for entry in self._pending:
                rows = entry[1].rows
                if entry[2] == group and not full and (
                        n_rows + rows <= limit or not picked):
                    picked.append(entry)
                    n_rows += rows
                else:
                    full = full or entry[2] == group
                    kept.append(entry)
            self._pending = collections.deque(kept)

            tickets = tuple(e[0] for e in picked)
            segs = [e[1] for e in picked]
            cbucket = bucket_for(n_rows, self.config.count_buckets)
            lbucket = bucket_for(max(int(np.diff(g.bounds).max())
                                     for g in segs),
                                 self.config.length_buckets)
            buf = self._buffers_for(cbucket, lbucket)
            for arr in buf.values():
                arr.fill(0)                  # NOP == 0, dt == 0, weight == 0
            r = 0
            for g in segs:
                for j in range(g.rows):
                    lo, hi = int(g.bounds[j]), int(g.bounds[j + 1])
                    for f in ("cmd", "bank", "row", "col", "data", "dt"):
                        buf[f][r, :hi - lo] = getattr(g.trace, f)[lo:hi]
                    buf["weight"][r, :hi - lo] = 1.0
                    r += 1
            # a window of one-row tickets carries nothing: its all-empty
            # carries stay on the device; otherwise they go up with the rows
            carry = self._empty_carries[cbucket]
            if n_rows > len(segs):
                carry = empty_carry(cbucket)
                for f, x in zip(SegmentCarry._fields, carry):
                    x[:r] = np.concatenate([getattr(g.carry, f)
                                            for g in segs])
            s.attrs.update(n_real=len(segs), rows=n_rows,
                           real_cmds=sum(g.n for g in segs),
                           slot_cmds=cbucket * lbucket,
                           wait_s=sum(s.t0 - e[3] for e in picked))
        with span("ring.transfer") as s:
            sent = [x for x in (*buf.values(), *carry)
                    if isinstance(x, np.ndarray)]
            dev, carry = jax.block_until_ready(jax.device_put((buf, carry)))
            s.attrs["bytes"] = sum(x.nbytes for x in sent)
        batch = CommandTrace(*(dev[f] for f in CommandTrace._fields))
        return RingBatch(TraceBatch(batch, dev["weight"], carry), tickets,
                         group, tuple(g.rows for g in segs))

    def _buffers_for(self, count: int, length: int) -> dict[str, np.ndarray]:
        buf = self._buffers.get((count, length))
        if buf is None:
            buf = {
                "cmd": np.zeros((count, length), np.int32),
                "bank": np.zeros((count, length), np.int32),
                "row": np.zeros((count, length), np.int32),
                "col": np.zeros((count, length), np.int32),
                "data": np.zeros((count, length, LINE_WORDS), np.uint32),
                "dt": np.zeros((count, length), np.int32),
                "weight": np.zeros((count, length), np.float32),
            }
            self._buffers[(count, length)] = buf
        return buf
