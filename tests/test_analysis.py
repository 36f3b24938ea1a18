"""Tests for the static-analysis layer (repro.analysis): the JEDEC trace
linter (seeded-mutation per-rule coverage + engine parity), the
compile-time dispatch auditor, and the repo AST lint."""
import ast
import textwrap
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis import dispatch_audit, repo_lint, trace_lint
from repro.core import dram, idd_loops, traces
from repro.core.dram import (ACT, NOP, PDE, PDE_SLOW, PDX, PRE, PREA, RD,
                             REF, SRE, SRX, TIMING, WR)
from repro.runtime.spans import RECORDER
from repro.serving.ring import segment

T = TIMING


def raw_trace(script):
    """Build a CommandTrace from (cmd, bank, dt) triples WITHOUT the
    construction-time low-power validation (the linter is the system under
    test; it must see illegal streams).  Host arrays: a device array of a
    new length would compile a program of its own inside a timed
    Hypothesis example."""
    cmd, bank, dt = (np.array(c, np.int32) for c in zip(*script))
    n = len(cmd)
    z = np.zeros(n, np.int32)
    return dram.CommandTrace(cmd, bank, z, z,
                             np.zeros((n, dram.LINE_WORDS), np.uint32), dt)


def fired(trace):
    """{(rule, cmd_index, bank)} from the numpy engine."""
    return {(d.rule, d.cmd_index, d.bank) for d in trace_lint.lint_trace(trace)}


# ---------------------------------------------------------------------------
# Per-rule seeded mutations: each entry is a minimal illegal stream plus the
# exact diagnostic it must produce (rule id, command index, bank).
# ---------------------------------------------------------------------------
SEEDED = {
    "tRCD": ([(ACT, 0, T.tRCD - 1), (RD, 0, 1)], 1, 0),
    "tRP": ([(ACT, 0, T.tRAS + 2), (PRE, 0, T.tRP - 1), (ACT, 0, 1)], 2, 0),
    "tRAS": ([(ACT, 0, T.tRAS - 1), (PRE, 0, 1)], 1, 0),
    "tRC": ([(ACT, 0, T.tRAS), (PRE, 0, T.tRP - 1), (ACT, 0, 1)], 2, 0),
    "tRRD": ([(ACT, 0, T.tRRD - 1), (ACT, 1, 1)], 1, 1),
    "tFAW": ([(ACT, 0, T.tRRD), (ACT, 1, T.tRRD), (ACT, 2, T.tRRD),
              (ACT, 3, T.tRRD - 1), (ACT, 4, 1)], 4, 4),
    "tWR": ([(ACT, 0, T.tRCD), (WR, 0, T.tBURST + T.tWR - 1),
             (PRE, 0, 1)], 2, 0),
    "tRTP": ([(ACT, 0, T.tRAS - T.tRTP + 1), (RD, 0, T.tRTP - 1),
              (PRE, 0, 1)], 2, 0),
    "tWTR": ([(ACT, 0, T.tRCD), (WR, 0, T.tBURST + T.tWTR - 1),
              (RD, 0, 1)], 2, 0),
    "tCCD": ([(ACT, 0, T.tRCD), (RD, 0, T.tCCD - 1), (RD, 0, 1)], 2, 0),
    "tRFC": ([(REF, 0, T.tRFC - 1), (ACT, 0, 1)], 1, 0),
    "tXP": ([(PDE, 0, T.tCKE), (PDX, 0, T.tXP - 1), (ACT, 0, 1)], 2, 0),
    "tXPDLL": ([(PDE_SLOW, 0, T.tCKE), (PDX, 0, T.tXPDLL - T.tRCD - 1),
                (ACT, 0, T.tRCD), (RD, 0, 1)], 3, 0),
    "tXS": ([(SRE, 0, T.tCKE), (SRX, 0, T.tXS - 1), (ACT, 0, 1)], 2, 0),
    "BANK_RW_CLOSED": ([(RD, 2, 1)], 0, 2),
    "BANK_ACT_OPEN": ([(ACT, 0, T.tRC), (ACT, 0, 1)], 1, 0),
    "REF_BANK_OPEN": ([(ACT, 0, T.tRAS), (REF, 0, 1)], 1, 0),
    "PDN_ILLEGAL_CMD": ([(PDE, 0, T.tCKE), (ACT, 0, 1)], 1, 0),
    "SR_ILLEGAL_CMD": ([(SRE, 0, T.tCKE), (ACT, 0, 1)], 1, 0),
    "DT_NEGATIVE": ([(NOP, 0, -1)], 0, 0),
}

#: rules whose minimal violation necessarily co-fires a second rule
#: (DDR3L-800 has tRAS + tRP == tRC and 4 * tRRD == tFAW exactly)
_COFIRE_OK = {"tRC", "tFAW", "BANK_ACT_OPEN"}


@pytest.mark.parametrize("rule_id", sorted(SEEDED))
def test_seeded_mutation_fires_exactly_that_rule(rule_id):
    script, idx, bank = SEEDED[rule_id]
    hits = fired(raw_trace(script))
    assert (rule_id, idx, bank) in hits, hits
    if rule_id not in _COFIRE_OK:
        assert hits == {(rule_id, idx, bank)}, hits


#: state-machine rules: no amount of waiting legalizes the stream, so the
#: stretch-by-one minimality probe below does not apply
_STATEFUL = {"DT_NEGATIVE", "BANK_RW_CLOSED", "BANK_ACT_OPEN",
             "REF_BANK_OPEN", "PDN_ILLEGAL_CMD", "SR_ILLEGAL_CMD"}


def test_seeded_mutations_are_minimal():
    """Stretching the violated slot by one cycle legalizes every timing
    seed (proof each seed sits exactly on the rule's boundary)."""
    for rule_id, (script, idx, _) in SEEDED.items():
        if rule_id in _STATEFUL:
            continue
        legal = [list(c) for c in script]
        legal[idx - 1][2] += 1
        hits = fired(raw_trace([tuple(c) for c in legal]))
        assert not hits, (rule_id, hits)


def test_trefi_is_a_warning_at_the_late_ref():
    tr = raw_trace([(NOP, 0, T.tREFI + trace_lint.REFI_SLACK + 10),
                    (REF, 0, 1)])
    diags = trace_lint.lint_trace(tr)
    assert [(d.rule, d.severity, d.cmd_index) for d in diags] == \
        [("tREFI", trace_lint.WARNING, 1)]


def test_diagnostic_carries_margin_and_message():
    script, idx, bank = SEEDED["tRCD"]
    (d,) = trace_lint.lint_trace(raw_trace(script))
    assert (d.rule, d.cmd_index, d.bank, d.margin) == ("tRCD", idx, bank, 1)
    assert "tRCD" in d.message and "#1" in d.message


# ---------------------------------------------------------------------------
# Property tests (hypothesis): seeded edits and engine parity
# ---------------------------------------------------------------------------
@settings(max_examples=20)
@given(gap=st.integers(min_value=1, max_value=T.tRP - 1))
def test_property_short_precharge_gap_fires_trp(gap):
    tr = raw_trace([(ACT, 0, T.tRC), (PRE, 0, gap), (ACT, 0, 1)])
    (d,) = trace_lint.lint_trace(tr)
    assert (d.rule, d.cmd_index, d.margin) == ("tRP", 2, T.tRP - gap)


@settings(max_examples=10)
@given(wait=st.integers(min_value=0, max_value=200))
def test_property_dropped_srx_fires_sr_illegal(wait):
    tr = raw_trace([(SRE, 0, T.tCKE), (NOP, 0, wait), (ACT, 0, 1)])
    hits = fired(tr)
    assert ("SR_ILLEGAL_CMD", 2, 0) in hits


_CMDS = st.sampled_from([NOP, ACT, PRE, RD, WR, REF, PDE, PDX, PREA,
                         PDE_SLOW, SRE, SRX])
_STEP = st.tuples(_CMDS, st.integers(min_value=0, max_value=7),
                  st.integers(min_value=0, max_value=2 * T.tRC))


@pytest.fixture(scope="module")
def compiled_lint_programs():
    """Both batched lint programs compiled at every shape an example can
    reach, so that no Hypothesis example is timed through a compile: one
    row or a stream cut into two, at every padded row length a 1-40 step
    script reaches (2 through 64), with no row, one row or both rows
    firing (``lint_rules`` then runs over 1 or 2 rows)."""
    for length in (2, 4, 8, 16, 32, 64):
        clean = [(NOP, 0, 1)] * length
        fires = [(RD, 0, 1)] + clean[1:]        # BANK_RW_CLOSED
        for script in (clean, fires):
            trace_lint.lint_traces([raw_trace(script)])
        for a, b in ((clean, clean), (clean, fires), (fires, fires)):
            trace_lint.lint_traces([segment(raw_trace(a + b),
                                            [0, length, 2 * length])])


@settings(max_examples=30)
@given(script=st.lists(_STEP, min_size=1, max_size=40),
       cut=st.integers(min_value=1, max_value=39))
def test_property_engines_agree_on_arbitrary_streams(compiled_lint_programs,
                                                      script, cut):
    """The vectorized numpy engine, the jitted batched engine (whole, and
    with the stream cut into two rows at ``cut``, the second carrying
    the first's state), and the independent reference walk produce
    identical diagnostics, in whole-stream indices, for ANY command
    stream, legal or not."""
    tr = raw_trace(script)
    key = lambda ds: sorted((d.rule, d.cmd_index, d.bank, d.margin)
                            for d in ds)
    vec = key(trace_lint.lint_trace(tr))
    ref = key(trace_lint.reference_lint(tr))
    bat = key(trace_lint.lint_traces([tr]))
    seg = key(trace_lint.lint_traces([
        segment(tr, [0, cut, len(script)]) if cut < len(script) else tr]))
    assert vec == ref == bat == seg


def full_program_diagnostics(traces):
    """Every row through ``lint_rules`` and extracted: the batched engine
    as it was before it counted first."""
    batch, _ = dram.batch_traces([(tr, 0) for tr in traces])
    cmd = np.asarray(batch.cmd)
    carry = np.tile(trace_lint.empty_carry(), (len(traces), 1))
    stacks = [np.asarray(x) for x in trace_lint._programs()[1](
        batch.cmd, batch.bank, batch.dt, carry)]
    return [d for ti in range(len(traces))
            for d in trace_lint._extract(*(x[ti] for x in stacks), cmd[ti],
                                         ti)]


def _seeded(*rule_ids):
    return [raw_trace(SEEDED[r][0]) for r in rule_ids]


def parity_window(name):
    clean = [idd_loops.idd2n(reps=2)]
    late_ref = raw_trace([(NOP, 0, T.tREFI + trace_lint.REFI_SLACK + 10),
                          (REF, 0, 1)])
    return {
        "mixed": _seeded("tRCD") + clean * 3 + _seeded("tFAW") + clean * 3
        + _seeded("REF_BANK_OPEN"),
        "warning-only": clean + [late_ref] + clean,
        "all-clean": clean * 5,
        "all-fire": _seeded(*sorted(SEEDED)),
        "five-of-nine-fire": _seeded("tRP", "tWR") + clean * 2
        + _seeded("tXS") + clean * 2 + _seeded("DT_NEGATIVE", "tCCD"),
    }[name]


@pytest.mark.parametrize("window", ["mixed", "warning-only", "all-clean",
                                    "all-fire", "five-of-nine-fire"])
def test_counting_engine_returns_the_full_programs_diagnostics(window):
    """Counting first and re-running only the traces that fired returns
    the very list the full program over every row gives, and the
    reference walk's per trace; ``fired_traces`` counts the traces with
    any diagnostic."""
    traces = parity_window(window)
    t0 = time.perf_counter()
    diags = trace_lint.lint_traces(traces)
    (fetch,) = [r for r in RECORDER.inside(t0, time.perf_counter())
                if r.name == "lint.fetch"]
    assert diags == full_program_diagnostics(traces)
    assert diags == [d for i, tr in enumerate(traces)
                     for d in trace_lint.reference_lint(tr, i)]
    assert fetch.attrs["fired_traces"] == len({d.trace_index
                                               for d in diags})
    assert bool(diags) == (window != "all-clean")


def test_batched_engine_reports_trace_index():
    bad = raw_trace(SEEDED["tRCD"][0])
    good = idd_loops.idd2n(reps=2)
    diags = trace_lint.lint_traces([good, bad, good])
    assert diags and all(d.trace_index == 1 for d in diags)


# ---------------------------------------------------------------------------
# Generator regressions: the exact illegal schedules this PR fixed, pinned
# to the rule that now catches them.
# ---------------------------------------------------------------------------
def test_old_naive_idd7_schedule_fires_tras():
    """Pre-fix IDD7 precharged each bank immediately after its read; the
    linter's tRAS rule is what makes that bug unrepresentable now."""
    script = []
    for b in range(8):
        script += [(ACT, b, T.tRCD), (RD, b, T.tCCD), (PRE, b, 1)]
    hits = fired(raw_trace(script))
    assert any(r == "tRAS" for r, _, _ in hits)


def test_old_tiled_idd3n_setup_fires_bank_act_open():
    """Pre-fix IDD3N tiled the all-banks ACT prologue into every loop rep,
    re-activating banks that were already open."""
    prologue = [(ACT, b, T.tRC) for b in range(8)]
    hits = fired(raw_trace(prologue * 2))
    assert any(r == "BANK_ACT_OPEN" for r, _, _ in hits)


def test_all_repo_generators_are_clean():
    """Every generator lints clean (they now self-check via
    check_generated, so construction succeeding is itself the assertion —
    this pins a couple of representative ones explicitly)."""
    for tr in (idd_loops.idd3n(reps=3), idd_loops.idd7(reps=2),
               traces.app_trace(traces.SPEC_APPS[0], n_requests=64)):
        assert trace_lint.lint_trace(tr) == []


# ---------------------------------------------------------------------------
# Ingestion guard (serve --power-report)
# ---------------------------------------------------------------------------
def test_serve_rejects_corrupt_trace_with_structured_error():
    from repro.launch import serve
    corrupt = raw_trace(SEEDED["tRCD"][0])
    good = idd_loops.idd0(reps=2)
    with pytest.raises(trace_lint.TraceProtocolError) as ei:
        serve.lint_ingested([good, corrupt])
    err = ei.value
    assert err.origin == "serve.power_report"
    (d,) = err.diagnostics
    assert (d.rule, d.trace_index, d.cmd_index, d.bank) == ("tRCD", 1, 1, 0)
    assert "tRCD" in str(err)


def test_check_generated_raises_and_is_disableable(monkeypatch):
    bad = raw_trace(SEEDED["tRAS"][0])
    with pytest.raises(trace_lint.TraceProtocolError):
        trace_lint.check_generated(bad, "test")
    monkeypatch.setenv("REPRO_TRACE_LINT", "off")
    assert trace_lint.check_generated(bad, "test") is bad


def test_make_trace_hook_is_opt_in(monkeypatch):
    cmds, banks, dts = zip(*SEEDED["tRCD"][0])
    dram.make_trace(list(cmds), list(banks), dts=list(dts))  # off: no raise
    monkeypatch.setenv("REPRO_TRACE_LINT", "strict")
    with pytest.raises(trace_lint.TraceProtocolError):
        dram.make_trace(list(cmds), list(banks), dts=list(dts))


# ---------------------------------------------------------------------------
# Dispatch audit
# ---------------------------------------------------------------------------
def test_dispatch_audit_clean_on_registered_impls(quick_vampire):
    tb = dispatch_audit.default_audit_batch()
    findings = []
    for impl in ("reference", "vectorized"):
        findings += dispatch_audit.audit_combination(
            quick_vampire, impl, "mean", tb)
    findings += dispatch_audit.audit_recompilation(
        quick_vampire, modes=("mean",), tb=tb)
    assert findings == []


def test_dispatch_audit_flags_dead_weight():
    """A dispatch that ignores the validity mask must be caught by DCE."""
    import jax
    jaxpr = jax.make_jaxpr(lambda x, w: x.sum())(
        np.ones(4, np.float32), np.ones(4, np.float32))
    used = dispatch_audit._dce_used_invars(jaxpr.jaxpr)
    assert used is not None and used == [True, False]


def test_dispatch_audit_flags_f64_text():
    assert dispatch_audit._F64_RE.search("tensor<4xf64>")
    assert not dispatch_audit._F64_RE.search("tensor<4xf32>")


# ---------------------------------------------------------------------------
# Repo lint
# ---------------------------------------------------------------------------
def _src(code):
    return [("core/sample.py", ast.parse(textwrap.dedent(code)))]


def test_repo_lint_clean_on_live_tree():
    assert repo_lint.errors_of(repo_lint.run_repo_lint()) == []


def test_repo_lint_flags_deprecated_shim_call():
    (f,) = repo_lint.check_no_deprecated_shims(
        _src("model.estimate_range_many(traces)"))
    assert f.rule == "no-deprecated-shims" and "estimate_range_many" \
        in f.message
    assert repo_lint.check_no_deprecated_shims(
        [("core/vampire.py", ast.parse("self.estimate_many(t)"))]) == []


def test_repo_lint_flags_modeless_impl():
    (f,) = repo_lint.check_impls_declare_modes(
        _src("register_impl(EstimateImpl(name='x', fn=f))"))
    assert f.rule == "impls-declare-modes"
    assert repo_lint.check_impls_declare_modes(
        _src("register_impl(EstimateImpl(name='x', modes=('mean',)))")) == []


def test_repo_lint_flags_module_level_interpret():
    (f,) = repo_lint.check_call_time_interpret(
        [("kernels/k.py", ast.parse("INTERPRET = True"))])
    assert f.rule == "call-time-interpret" and "INTERPRET" in f.message
    (f,) = repo_lint.check_call_time_interpret(
        [("kernels/k.py", ast.parse("y = pl.pallas_call(f)(x)"))])
    assert "interpret_default" in f.message
    assert repo_lint.check_call_time_interpret(
        [("kernels/k.py", ast.parse(
            "y = pl.pallas_call(f, interpret=interpret_default())(x)"))]) == []


def test_repo_lint_params_coverage_negative(tmp_path):
    (tmp_path / "core").mkdir()
    (tmp_path / "core" / "energy_model.py").write_text(textwrap.dedent("""
        class PowerParams(NamedTuple):
            a: int
            b: int
            orphan: int
            late: int
    """))
    (tmp_path / "core" / "model_api.py").write_text(textwrap.dedent("""
        _FITTED_FIELDS = ("a", "late")
        def _save_v1_pickle(m):
            blob = {"a": m.a, "k1": 0, "k2": 0, "k3": 0, "k4": 0}
    """))
    (tmp_path / "core" / "characterize.py").write_text(textwrap.dedent("""
        def build_params(x):
            return PowerParams(b=x)
    """))
    findings = repo_lint.check_params_serialization(tmp_path)
    msgs = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "orphan" in msgs          # neither fitted nor derived
    assert "late" in msgs            # fitted, post-v1, no default


def test_repo_lint_params_coverage_live():
    assert repo_lint.check_params_serialization() == []
