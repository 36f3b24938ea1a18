"""Open loop with Poisson arrivals, through the estimation service: each
request is one ``submit`` when it is due; the single-threaded loop
submits what is due, then calls ``step`` while the ring holds traces,
and takes every answer with ``result``.  Each request is timed from when
it was due to when its answer came back; requests still queued when the
arrivals end are drained and keep their latency.

Traffic keys: ``mode``, ``rate_per_s`` (fixed, about three quarters
of the highest rate the service sustains on the chip, found by
``sweep.py``),
``pool_traces`` with ``length_min``/``length_max``, ``check_sample``.
The gaps between arrivals are the quantiles of the exponential law at
that rate, shuffled by the seed: every seed offers the same gaps and
sizes, in another order.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from chipbench import spec_gen
from chipbench.harness import Outcome


class Driver:
    def __init__(self, system, traffic: dict, seed: int, spans):
        self.system, self.traffic, self.spans = system, traffic, spans
        self.surface = traffic["mode"] == "surface"
        self.rate = float(traffic["rate_per_s"])
        self.pool = spec_gen.trace_pool(traffic["pool_traces"],
                                        traffic["length_min"],
                                        traffic["length_max"], seed)
        self.requests = [system.request(t) for t in self.pool]
        self.rng = np.random.default_rng(np.random.SeedSequence([43, seed]))
        self.service = system.service(traffic["mode"])
        self.answers: list = []

    def warm(self) -> None:
        """Every pool trace submitted alone (each admission's own
        shapes), then windows of every count bucket of the ring for every
        length bucket the pool reaches."""
        svc = self.service
        groups = [list(range(len(self.pool)))]
        lengths = np.asarray([len(t["cmd"]) for t in self.pool])
        edges = (0,) + tuple(svc.ring.config.length_buckets)
        for lo, hi in zip(edges[:-1], edges[1:]):
            members = np.flatnonzero((lengths > lo) & (lengths <= hi))
            if len(members):
                groups += [np.resize(members, count).tolist()
                           for count in svc.ring.config.count_buckets]
        for group in groups:
            tickets = [svc.submit(self.requests[j]) for j in group]
            while svc.step():
                pass
            for t in tickets:
                svc.result(t)

    def arrivals(self, seconds: float) -> np.ndarray:
        n = max(int(self.rate * seconds), 1)
        q = (np.arange(n) + 0.5) / n
        gaps = self.rng.permutation(-np.log1p(-q) / self.rate)
        due = np.cumsum(gaps)
        return due[due < seconds]

    def run(self, seconds: float) -> Outcome:
        svc, spans = self.service, self.spans
        due = self.arrivals(seconds)
        which = self.rng.integers(0, len(self.pool), size=len(due))
        pending: collections.deque = collections.deque()
        latencies, late = [], []
        failed = cmds = depth_max = 0
        k = 0
        start = time.perf_counter()
        while k < len(due) or pending:
            now = time.perf_counter() - start
            while k < len(due) and due[k] <= now:
                with spans("admit"):
                    t = svc.submit(self.requests[which[k]])
                late.append(time.perf_counter() - start - due[k])
                if isinstance(t, int):
                    pending.append((t, k))
                else:
                    failed += 1
                k += 1
            depth_max = max(depth_max, len(pending))
            if len(svc.ring):
                with spans("step"):
                    while svc.step():
                        pass
                with spans("result"):
                    for _ in range(len(pending)):
                        t, j = pending.popleft()
                        try:
                            row = svc.result(t)
                        except KeyError:
                            pending.append((t, j))
                            continue
                        latencies.append(time.perf_counter() - start - due[j])
                        i = int(which[j])
                        self.answers.append((i, self.system.answer(row)))
                        cmds += len(self.pool[i]["cmd"])
            elif pending:
                failed += len(pending)          # queued nowhere: lost
                pending.clear()
            elif k < len(due):
                with spans("idle_wait"):
                    wait = due[k] - (time.perf_counter() - start)
                    if wait > 0:
                        time.sleep(wait)
        window_s = time.perf_counter() - start
        late_ms = np.asarray(late) * 1e3
        return Outcome(window_s, len(due), failed, {
            "requests": len(due), "answers": len(latencies),
            "real_commands": cmds, "sets": self.system.sets,
            "surface": self.surface, "rate_per_s": self.rate,
            "queue_max": depth_max,
            "generator_late_ms_p50": float(np.percentile(late_ms, 50))
            if len(late_ms) else 0.0,
            "generator_late_ms_max": float(late_ms.max())
            if len(late_ms) else 0.0}, latencies)

    def sample(self, rng) -> list:
        from chipbench.loops.common import sample_answers
        return sample_answers(self.answers, self.pool,
                              self.traffic["check_sample"], rng,
                              self.surface)

    def release(self) -> None:
        self.service = None
        self.system.release()
