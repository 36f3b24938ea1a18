"""Closed loop of back-to-back fleet surface maps: each dispatch sends
one batch of the SPEC apps' trace segments, charged against every
module, and takes the whole report back to the host.

Traffic keys: ``segment_length`` (commands per segment), ``rows`` (the
batch's trace rows; the rows past the apps are zero-weight padding),
``batches`` (distinct batches, dispatched in turn: batch ``b`` holds
every app's segment ``b``, in an order shuffled by the seed),
``check_sample`` (answers compared: one (segment, every module) row
each), ``keep`` (dispatches whose reports are kept for the check, drawn
uniformly from the window by reservoir sampling).
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import reference, spec_gen
from chipbench.harness import Outcome


class Driver:
    def __init__(self, system, traffic: dict, seed: int, spans):
        self.system, self.traffic, self.spans = system, traffic, spans
        rng = np.random.default_rng(np.random.SeedSequence([47, seed]))
        n_apps, rows = len(spec_gen.SPEC_APPS), traffic["rows"]
        length = traffic["segment_length"]
        self.segments, self.batches = [], []
        for b in range(traffic["batches"]):
            segs = [spec_gen.app_trace(int(a), length, seed, b)
                    for a in rng.permutation(n_apps)]
            fields = {f: np.zeros((rows, length) + segs[0][f].shape[1:],
                                  segs[0][f].dtype) for f in spec_gen.FIELDS}
            weight = np.zeros((rows, length), np.float32)
            for i, s in enumerate(segs):
                n = len(s["cmd"])
                for f in spec_gen.FIELDS:
                    fields[f][i, :n] = s[f]
                weight[i, :n] = 1.0
            self.segments.append(segs)
            self.batches.append((fields, weight,
                                 sum(len(s["cmd"]) for s in segs)))
        self.rng = rng
        self.kept: list = []

    def _dispatch(self, b: int) -> dict:
        import jax.numpy as jnp

        from repro.core.dram import CommandTrace
        fields, weight, _ = self.batches[b]
        with self.spans("dispatch"):
            trace = CommandTrace(*(jnp.asarray(fields[f])
                                   for f in spec_gen.FIELDS))
            rep = self.system.surface(trace, jnp.asarray(weight))
            return {leaf: np.asarray(getattr(rep, leaf))
                    for leaf in reference.LEAVES}

    def warm(self) -> None:
        for b in range(len(self.batches)):
            self._dispatch(b)

    def run(self, seconds: float) -> Outcome:
        keep = self.traffic["keep"]
        dispatches = cmds = 0
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            b = dispatches % len(self.batches)
            out = self._dispatch(b)
            # reservoir sampling: every dispatch kept with equal chance
            if len(self.kept) < keep:
                self.kept.append((b, out))
            else:
                j = int(self.rng.integers(0, dispatches + 1))
                if j < keep:
                    self.kept[j] = (b, out)
            dispatches += 1
            cmds += self.batches[b][2]
        window_s = time.perf_counter() - start
        sets = self.system.sets
        answers = dispatches * len(spec_gen.SPEC_APPS)
        return Outcome(window_s, dispatches, 0, {
            "dispatches": dispatches, "answers": answers,
            "real_commands": cmds, "module_commands": cmds * sets,
            "sets": sets, "surface": True})

    def sample(self, rng) -> list:
        rows = [(b, i, out) for b, out in self.kept
                for i in range(len(self.segments[b]))]
        if not rows:
            return []
        picked = rng.choice(len(rows), size=min(self.traffic["check_sample"],
                                                len(rows)), replace=False)
        return [(self.segments[rows[j][0]][rows[j][1]],
                 {leaf: v[rows[j][1]] for leaf, v in rows[j][2].items()},
                 True) for j in sorted(picked.tolist())]

    def release(self) -> None:
        self.system.release()
