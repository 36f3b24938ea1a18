"""Whole traces served as segments: a trace past the ring's largest length
bucket is cut into rows, each row carries the state of the commands before
it, and the ticket's rows sum to the one-shot integration of the whole
trace.  The reference is the whole, unsplit trace through
``estimate(..., impl='reference')`` (the per-command scan) and, for the
lint, ``reference_lint``."""
import numpy as np
import pytest

from repro.analysis import trace_lint
from repro.core import baselines_power, dram
from repro.core.dram import (ACT, NOP, PDE, PDE_SLOW, PDX, PRE, PREA, RD,
                             REF, SRE, SRX, TIMING, WR)
from repro.core.energy_model import empty_carry
from repro.core.estimate_batch import TraceBatch
from repro.serving import EstimationService, RingConfig, ServiceConfig
from repro.serving.ring import segment

T = TIMING
RING = RingConfig(length_buckets=(64, 128), count_buckets=(4, 8))
ROW = RING.max_length

#: The segmented reports against the whole trace's, as the widest gap
#: over a leaf relative to the leaf's largest value (the benchmark's
#: measure).  Both sides sum the same per-command float32 charges in
#: another order: ~1e-7 of the total on these traces.  1e-5 leaves 100x of
#: room, and a cut that drops its carry misses by 3e-3 or more (the
#: planted fault below), 300x above it.
MAX_GAP = 1e-5


def gap(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


# ---------------------------------------------------------------------------
# Seeded traces with cuts placed on state changes
# ---------------------------------------------------------------------------
def _events(rng, n):
    """(cmd, bank, dt) of a random stream with properly nested low-power
    states (the integrator reads no timing, so the gaps are random)."""
    out, open_ = [], [False] * 8

    def emit(c, b=0, dt=None):
        out.append((c, b, int(rng.integers(0, 40)) if dt is None else dt))

    while len(out) < n:
        r = rng.random()
        if r < 0.55:
            b = int(rng.integers(8))
            if not open_[b] or rng.random() < 0.2:
                if open_[b]:
                    emit(PRE, b)
                emit(ACT, b)
                open_[b] = True
            emit(RD if rng.random() < 0.6 else WR, b)
        elif r < 0.65:
            emit(PREA)
            open_ = [False] * 8
            emit(REF)
        elif r < 0.75:                      # fast, or active with banks open
            emit(PDE)
            emit(NOP, dt=int(rng.integers(0, 200)))
            emit(PDX)
        elif r < 0.82:
            emit(PREA)
            open_ = [False] * 8
            emit(PDE_SLOW)
            emit(NOP)
            emit(PDX)
        elif r < 0.9:
            emit(PREA)
            open_ = [False] * 8
            emit(SRE)
            emit(NOP, dt=int(rng.integers(0, 3000)))
            emit(SRX)
        else:
            emit(NOP)
    return out


def _where(events, stage):
    """Positions p where a cut before command p lands on ``stage``."""
    cmd = [c for c, _, _ in events]
    bank = [b for _, b, _ in events]
    hits, in_pdn, in_sr, open_, last_rw = [], False, False, set(), None
    for p, c in enumerate(cmd):
        if stage == "power-down" and in_pdn:
            hits.append(p)
        elif stage == "self-refresh" and in_sr:
            hits.append(p)
        elif stage == "banks-open" and open_ and not (in_pdn or in_sr):
            hits.append(p)
        elif (stage == "between-reads" and c in (RD, WR)
              and last_rw is not None and last_rw != bank[p]):
            hits.append(p)
        elif stage == "after-ref" and p and cmd[p - 1] == REF:
            hits.append(p)
        if c == ACT:
            open_.add(bank[p])
        elif c == PRE:
            open_.discard(bank[p])
        elif c == PREA:
            open_.clear()
        elif c in (PDE, PDE_SLOW):
            in_pdn = True
        elif c == PDX:
            in_pdn = False
        elif c == SRE:
            in_sr = True
        elif c == SRX:
            in_sr = False
        if c in (RD, WR):
            last_rw = bank[p]
    return hits


STAGES = ("power-down", "self-refresh", "banks-open", "between-reads",
          "after-ref")


def staged_trace(seed: int, stages=STAGES) -> dram.CommandTrace:
    """A seeded trace whose ring cuts (every ``ROW`` commands) fall on the
    given states: each row ends just before a command where the state
    holds, padded to ``ROW`` with zero-cycle NOPs (inert: no charge, no
    state, no time)."""
    rng = np.random.default_rng(seed)
    events = _events(rng, 2000)
    rows, start = [], 0
    for stage in stages:
        cut = next(p for p in _where(events, stage)
                   if start + ROW // 2 <= p <= start + ROW - 1)
        rows.append(events[start:cut] + [(NOP, 0, 0)] * (ROW - cut + start))
        start = cut
    rows.append(events[start:start + ROW // 2])
    script = [e for row in rows for e in row]
    cmd, bank, dt = (np.asarray(x, np.int32) for x in zip(*script))
    n = len(cmd)
    row = rng.integers(0, 1 << 15, size=n).astype(np.int32)
    col = rng.integers(0, 4, size=n).astype(np.int32)    # repeats: IL_NONE
    data = rng.integers(0, 2**32, size=(n, dram.LINE_WORDS),
                        dtype=np.uint64).astype(np.uint32)
    data[~np.isin(cmd, (RD, WR))] = 0
    return dram.CommandTrace(cmd, bank, row, col, data, dt)


def segmented_batch(tr: dram.CommandTrace, bounds) -> TraceBatch:
    """``tr`` cut into rows at the ascending ``bounds`` (0 first, its
    length last), each row with its segment carry."""
    bounds = [int(b) for b in bounds]
    rows = [dram.CommandTrace(*(np.asarray(x)[lo:hi] for x in tr))
            for lo, hi in zip(bounds[:-1], bounds[1:])]
    batch = TraceBatch.from_traces(rows)
    return TraceBatch(batch.trace, batch.weight, segment(tr, bounds).carry)


def _estimators(vampire):
    return {"vampire": vampire,
            "micron": baselines_power.MicronModel.from_vampire(vampire),
            "drampower": baselines_power.DRAMPowerModel.from_vampire(vampire)}


_KW = {"distribution": dict(ones_frac=0.4, toggle_frac=0.3)}


def _reports(rep, mode):
    return rep if mode == "range" else (rep,)


@pytest.mark.parametrize("mode", ["mean", "range", "distribution",
                                  "surface"])
@pytest.mark.parametrize("kind", ["vampire", "micron", "drampower"])
def test_served_segments_equal_the_whole_trace(quick_vampire, kind, mode):
    """Every impl through the service: a trace cut into 6 rows at the
    ring's largest bucket, cuts inside power-down and self-refresh, with
    banks open, between reads of two banks and just after a REF, gets one
    report equal to the whole trace's 'reference' integration."""
    model = _estimators(quick_vampire)[kind]
    tr = staged_trace(7)
    assert int(tr.n) == 5 * ROW + ROW // 2
    whole = model.estimate([tr], mode=mode, impl="reference",
                           **_KW.get(mode, {}))
    for impl in ("vectorized", "pallas", "reference"):
        svc = EstimationService(model, ServiceConfig(
            ring=RING, mode=mode, impl=impl, lint=False, **_KW.get(mode, {})))
        ticket = svc.submit(tr)
        assert svc.step() == 1 and len(svc.ring) == 0
        got = svc.result(ticket)
        for g, w in zip(_reports(got, mode), _reports(whole, mode)):
            for f in w._fields:
                assert gap(getattr(g, f), np.asarray(getattr(w, f))[0]) \
                    <= MAX_GAP, (impl, f)
            np.testing.assert_array_equal(g.cycles, np.asarray(w.cycles)[0])


@pytest.mark.parametrize("kind", ["vampire", "micron", "drampower"])
def test_dropped_carry_fails_the_tolerance(quick_vampire, kind):
    """The planted fault: the same rows without their carries (each row
    starting cold) miss the whole trace by more than ``MAX_GAP``, for every
    impl — the tolerance can see a lost carry."""
    model = _estimators(quick_vampire)[kind]
    tr = staged_trace(7)
    bounds = np.arange(0, int(tr.n) + ROW, ROW).clip(max=int(tr.n))
    tb = segmented_batch(tr, bounds)
    whole = model.estimate([tr], impl="reference")
    want = np.asarray(whole.charge_ma_cycles)[0]
    for impl in ("vectorized", "pallas", "reference"):
        carried = model.estimate(tb, impl=impl)
        cold = model.estimate(TraceBatch(tb.trace, tb.weight), impl=impl)
        assert gap(np.asarray(carried.charge_ma_cycles).sum(0), want) \
            <= MAX_GAP
        assert gap(np.asarray(cold.charge_ma_cycles).sum(0), want) \
            > 10 * MAX_GAP, impl


@pytest.mark.parametrize("stage", STAGES)
def test_each_cut_needs_its_carry(quick_vampire, stage):
    """One cut on one state change: with the carry the two rows sum to the
    whole trace; dropped at that one cut, the gap exceeds the tolerance,
    so each kind of carried state is seen by the parity tests."""
    tr = staged_trace(11, stages=(stage,))
    bounds = [0, ROW, int(tr.n)]
    tb = segmented_batch(tr, bounds)
    want = np.asarray(quick_vampire.estimate(
        [tr], impl="reference").charge_ma_cycles)[0]
    carried = quick_vampire.estimate(tb)
    cold = quick_vampire.estimate(TraceBatch(tb.trace, tb.weight))
    assert gap(np.asarray(carried.charge_ma_cycles).sum(0), want) <= MAX_GAP
    assert gap(np.asarray(cold.charge_ma_cycles).sum(0), want) > MAX_GAP


def test_one_row_is_exactly_the_unsegmented_path(quick_vampire):
    """The empty carry is the identity: a one-row batch with it gives the
    bits an uncarried batch gives, in every impl."""
    tr = staged_trace(3, stages=())
    tb = segmented_batch(tr, [0, int(tr.n)])
    assert all(np.array_equal(np.asarray(a), b[None]) for a, b in
               zip(tb.carry, empty_carry()))
    for impl in ("vectorized", "pallas", "reference"):
        a = quick_vampire.estimate(tb, impl=impl)
        b = quick_vampire.estimate(TraceBatch(tb.trace, tb.weight),
                                   impl=impl)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_carries_come_from_the_trace_alone():
    """The carry of a cut is the state before it: the first row's carry
    is empty, and a carry read at every cut equals one read at that cut
    alone (a scan over summaries, not over earlier estimates)."""
    tr = staged_trace(5)
    n = int(tr.n)
    every = segment(tr, list(range(0, n, ROW)) + [n])
    for j, s in enumerate(every.bounds[1:-1], 1):
        alone = segment(tr, [0, s, n])
        for a, b in zip(every.carry, alone.carry):
            np.testing.assert_array_equal(np.asarray(a)[j], np.asarray(b)[1])
        np.testing.assert_array_equal(every.lint_carry[j],
                                      alone.lint_carry[1])
    assert not every.carry.has_prev[0] and not every.carry.open[0].any()
    np.testing.assert_array_equal(every.lint_carry[0],
                                  trace_lint.empty_carry())


# ---------------------------------------------------------------------------
# The lint across cuts
# ---------------------------------------------------------------------------
def _script(script):
    cmd, bank, dt = (np.asarray(x, np.int32) for x in zip(*script))
    z = np.zeros(len(cmd), np.int32)
    return dram.CommandTrace(cmd, bank, z, z,
                             np.zeros((len(cmd), dram.LINE_WORDS),
                                      np.uint32), dt)


def _key(diags):
    return sorted((d.trace_index, d.rule, d.cmd_index, d.bank, d.margin)
                  for d in diags)


#: violations that span a cut: (script, cut positions, (rule, index))
SPANNING = {
    "tRCD": ([(ACT, 0, T.tRCD - 1), (RD, 0, 1)], [1], ("tRCD", 1)),
    "tFAW": ([(ACT, 0, T.tRRD), (ACT, 1, T.tRRD), (ACT, 2, T.tRRD),
              (ACT, 3, T.tRRD - 1), (ACT, 4, 1)], [2, 4], ("tFAW", 4)),
    "tRFC": ([(REF, 0, T.tRFC - 1), (ACT, 0, 1)], [1], ("tRFC", 1)),
    "tXP": ([(PDE, 0, T.tCKE), (PDX, 0, T.tXP - 1), (ACT, 0, 1)], [1, 2],
            ("tXP", 2)),
    # the late REF is anchored on a REF two segments back
    "tREFI": ([(REF, 0, T.tRFC), (NOP, 0, T.tREFI), (NOP, 0,
                                                     trace_lint.REFI_SLACK),
               (REF, 0, 1)], [1, 3], ("tREFI", 3)),
}


@pytest.mark.parametrize("rule", sorted(SPANNING))
def test_violation_across_a_cut_is_reported_once_at_its_index(rule):
    script, cuts, (rule_id, index) = SPANNING[rule]
    tr = _script(script)
    bounds = [0] + cuts + [int(tr.n)]
    got = trace_lint.lint_traces([segment(tr, bounds)])
    assert _key(got) == _key(trace_lint.reference_lint(tr))
    assert [(d.rule, d.cmd_index) for d in got
            if d.rule == rule_id] == [(rule_id, index)]


def test_cuts_create_no_false_violations():
    """A legal trace cut everywhere lints clean, as it does whole."""
    from repro.core import idd_loops
    tr = idd_loops.validation_sweep(16)
    assert trace_lint.lint_traces([tr]) == []
    for step in (1, 3, 7, 16, 64):
        bounds = list(range(0, int(tr.n), step)) + [int(tr.n)]
        assert trace_lint.lint_traces([segment(tr, bounds)]) == [], step


def test_service_lints_the_rows_in_whole_trace_indices(quick_vampire):
    """A violation deep in a long trace rejects the ticket with its
    whole-trace index; the legal trace beside it is served."""
    from repro.core import idd_loops
    clean = idd_loops.validation_sweep(32)                # 272: 3 rows
    bad = _script([(NOP, 0, 1)] * 200 + [(ACT, 0, T.tRCD - 1), (RD, 0, 1)])
    svc = EstimationService(quick_vampire, ServiceConfig(ring=RING))
    tickets, rejections = svc.submit_many([bad, clean])
    (r,) = rejections
    assert tickets[0] is None and r.rules == ("tRCD",)
    assert [d.cmd_index for d in r.diagnostics] == [201]
    svc.drain()
    assert np.asarray(svc.result(tickets[1]).energy_pj).shape == (3,)


def _long_idle_trace(units: int = 12, dwell: int = 1 << 28):
    """A legal trace of ``units`` blocks of 40 IDD0 ACT/PRE pairs, each
    followed by a self-refresh dwell of ``dwell`` cycles: 84 commands a
    block, so a ring row of 128 holds at most two dwells, while the whole
    trace spans ``units * dwell`` cycles and more."""
    from repro.core import idd_loops
    sr = idd_loops.idd6(reps=1)
    dt = np.asarray(sr.dt).copy()
    dt[np.asarray(sr.cmd) == NOP] = dwell
    unit = [idd_loops.idd0(reps=40), sr._replace(dt=dt)]
    return dram.CommandTrace(*(np.concatenate([np.asarray(x) for x in f])
                               for f in zip(*(unit * units))))


def test_a_trace_past_two_to_the_31_cycles_is_served_whole(quick_vampire):
    """Row-local lint time and int64 cycle sums: a legal trace of 3.2e9
    cycles (past 2^30, where whole-trace time held in int32 would wrap
    the lint's "never happened" sentinel, and past 2^31, where summed
    int32 cycles would) is admitted and gets the whole trace's report;
    a tRCD violation after it is reported at its whole-trace index with
    reference_lint's margin."""
    tr = _long_idle_trace()
    n = int(tr.n)
    cycles = int(np.sum(np.asarray(tr.dt), dtype=np.int64))
    assert n <= RING.max_commands and cycles > 3 * (1 << 30)
    bad = dram.CommandTrace(*(np.concatenate([np.asarray(a), b]) for a, b in
                              zip(tr, _script([(ACT, 0, T.tRCD - 1),
                                               (RD, 0, 1)]))))
    assert trace_lint.reference_lint(tr) == []
    svc = EstimationService(quick_vampire, ServiceConfig(ring=RING))
    (ticket, none), (r,) = svc.submit_many([tr, bad])
    assert none is None and r.rules == ("tRCD",)
    assert [(d.rule, d.cmd_index, d.bank, d.margin)
            for d in r.diagnostics] == [
        (d.rule, d.cmd_index, d.bank, d.margin)
        for d in trace_lint.reference_lint(bad)] == [("tRCD", n + 1, 0, 1)]
    svc.drain()
    got = svc.result(ticket)
    whole = quick_vampire.estimate([tr], impl="reference")
    want = np.asarray(whole.charge_ma_cycles)[0]
    assert gap(got.charge_ma_cycles, want) <= MAX_GAP
    np.testing.assert_array_equal(got.cycles, cycles)
    np.testing.assert_allclose(got.avg_current_ma, want / cycles, rtol=1e-5)
    np.testing.assert_allclose(got.time_ns, cycles * dram.TCK_NS, rtol=1e-6)
