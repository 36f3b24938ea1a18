"""Closed loop with a full backlog, through the estimation service: each
window is one ``submit_many`` of a batch of traces, ``step`` until the
ring is empty, then ``result`` for every ticket.

Traffic keys: ``mode`` (the report mode served), ``window_traces``,
``pool_traces`` with ``length_min``/``length_max`` (the seeded SPEC-mix
pool, :func:`chipbench.spec_gen.trace_pool`), ``schedule_epochs`` (the
window schedule is that many shuffles of the pool, cut into windows and
repeated), ``check_sample``.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import spec_gen
from chipbench.harness import Outcome


class Driver:
    def __init__(self, system, traffic: dict, seed: int, spans):
        self.system, self.traffic, self.spans = system, traffic, spans
        self.surface = traffic["mode"] == "surface"
        self.pool = spec_gen.trace_pool(traffic["pool_traces"],
                                        traffic["length_min"],
                                        traffic["length_max"], seed)
        self.requests = [system.request(t) for t in self.pool]
        rng = np.random.default_rng(np.random.SeedSequence([41, seed]))
        per = traffic["window_traces"]
        n = len(self.pool) - len(self.pool) % per
        self.schedule = [perm[i:i + per]
                         for perm in (rng.permutation(len(self.pool))[:n]
                                      for _ in range(
                                          traffic["schedule_epochs"]))
                         for i in range(0, n, per)]
        self.service = system.service(traffic["mode"])
        self.answers: list = []

    def _window(self, idx) -> tuple[int, int, int]:
        """One window; returns (answered, real commands, failed)."""
        svc = self.service
        with self.spans("admit"):
            tickets, _ = svc.submit_many([self.requests[i] for i in idx])
        with self.spans("step"):
            while svc.step():
                pass
        answered = cmds = failed = 0
        with self.spans("result"):
            for i, t in zip(idx, tickets):
                try:
                    row = svc.result(t) if t is not None else None
                except KeyError:
                    row = None
                if row is None:
                    failed += 1
                    continue
                self.answers.append((int(i), self.system.answer(row)))
                answered += 1
                cmds += len(self.pool[i]["cmd"])
        return answered, cmds, failed

    def warm(self) -> None:
        """One pass over the schedule: every shape the window uses."""
        for idx in self.schedule:
            self._window(idx)
        self.answers.clear()

    def run(self, seconds: float) -> Outcome:
        attempted = answered = cmds = failed = windows = 0
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            idx = self.schedule[windows % len(self.schedule)]
            a, c, f = self._window(idx)
            attempted += len(idx)
            answered += a
            cmds += c
            failed += f
            windows += 1
        window_s = time.perf_counter() - start
        return Outcome(window_s, attempted, failed, {
            "windows": windows, "answers": answered,
            "real_commands": cmds, "sets": self.system.sets,
            "surface": self.surface})

    def sample(self, rng) -> list:
        from chipbench.loops.common import sample_answers
        return sample_answers(self.answers, self.pool,
                              self.traffic["check_sample"], rng,
                              self.surface)

    def release(self) -> None:
        self.service = None
        self.system.release()
