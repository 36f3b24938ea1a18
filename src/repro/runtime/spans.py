"""The program's one span recorder: timed host work at layer boundaries.

``with span("ring.repad") as s:`` records ``(name, t0, t1, attrs)`` into
:data:`RECORDER` when the block exits:

* ``t0``/``t1`` are ``time.perf_counter()`` readings, so a reader can cut
  the records to any window timed on the same clock;
* ``attrs`` are small integer or float counts (bytes moved, real against
  padded slots) that the block sets on ``s.attrs`` before it exits.

Each span also enters ``jax.profiler.TraceAnnotation(name)``, so while a
profiler session runs it lands on the trace's ``/host:CPU`` plane, on the
device trace's clock.  Without a session that costs under a microsecond.

A ``jax.monitoring`` listener adds one ``jax.compile`` record per XLA
backend compile (persistent-cache loads included), ending when the
compile ended, so a compile inside a measured window shows beside the
idle time it causes; another counts persistent-cache hits.

The recorder is always on.  Records go into a bounded deque of
:data:`CAPACITY` entries; the oldest are dropped first, and
:meth:`Recorder.inside` refuses a window that may have lost records.
They are kept as flat tuples of atoms (the attrs' keys and values as two
tuples), which the garbage collector stops tracking at its next passes:
kept records never lengthen a full collection, which would stall the
served path.  Spans sit only at host boundaries, never inside a jitted
function.
"""
from __future__ import annotations

import collections
import time
from typing import NamedTuple

import jax

#: records kept: a 40 s open-loop window at 192 requests/s (nine spans
#: each) and its warm-up fit with room to spare
CAPACITY = 1 << 17
#: the name of the records the compile listener adds
COMPILE = "jax.compile"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Record(NamedTuple):
    """One span, as :meth:`Recorder.inside` returns it."""
    name: str
    t0: float
    t1: float
    attrs: dict


class Recorder:
    """A bounded log of spans, in the order they ended."""

    def __init__(self, capacity: int = CAPACITY):
        #: ``(name, t0, t1, attr keys, attr values)`` tuples
        self.records: collections.deque = collections.deque(maxlen=capacity)
        #: the latest end time of any dropped record
        self.dropped_until = float("-inf")
        self.cache_hits = 0

    def add(self, name: str, t0: float, t1: float, attrs: dict) -> None:
        if len(self.records) == self.records.maxlen:
            self.dropped_until = max(self.dropped_until, self.records[0][2])
        self.records.append((name, t0, t1, tuple(attrs),
                             tuple(attrs.values())))

    def inside(self, t0: float, t1: float) -> list[Record] | None:
        """The records that started at or after ``t0`` and ended by
        ``t1``, or ``None`` when a dropped record may have been one."""
        if self.dropped_until >= t0:
            return None
        return [Record(n, a, b, dict(zip(keys, values)))
                for n, a, b, keys, values in self.records
                if a >= t0 and b <= t1]


#: the process's recorder
RECORDER = Recorder()


class span:
    """``span(name, **attrs)``: a context manager that records the block
    it wraps (module doc)."""
    __slots__ = ("name", "attrs", "t0", "_annotation")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> span:
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        RECORDER.add(self.name, self.t0, t1, self.attrs)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == BACKEND_COMPILE_EVENT:
        now = time.perf_counter()
        RECORDER.add(COMPILE, now - secs, now, {})


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        RECORDER.cache_hits += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
