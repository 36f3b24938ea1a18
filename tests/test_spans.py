"""The span recorder (``repro.runtime.spans``) and the spans, counters and
kernel names it gives the served and fleet paths."""
import gc
import glob
import importlib.util
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import trace_lint
from repro.core import fleet, idd_loops
from repro.core.dram import CommandTrace, batch_traces
from repro.runtime import spans
from repro.runtime.spans import COMPILE, RECORDER, Record, Recorder, span
from repro.serving import EstimationService, ServiceConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
SERVE_SPANS = ["trace.segment", "lint.pack", "lint.rules", "lint.fetch",
               "lint.extract", "ring.repad", "ring.transfer",
               "engine.dispatch", "engine.block", "service.combine",
               "service.slice"]


def _since(t0: float) -> list[Record]:
    recs = RECORDER.inside(t0, time.perf_counter())
    assert recs is not None
    return recs


def _program_span_names() -> set[str]:
    """Every span name the program's sources open."""
    names = {COMPILE}
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        names.update(re.findall(r'\bspan\("([^"]+)"', path.read_text()))
    return names


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------
def test_span_records_nesting_parent_and_attrs():
    t0 = time.perf_counter()
    with span("test.outer"):
        with span("test.inner", calls=1) as s:
            s.attrs["bytes"] = 64
    inner, outer = [r for r in _since(t0) if r.name.startswith("test.")]
    assert inner.name == "test.inner"
    assert inner.attrs == {"calls": 1, "bytes": 64}
    assert (outer.name, outer.attrs) == ("test.outer", {})
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_span_records_even_when_the_block_raises():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with span("test.raises"):
            raise ValueError("boom")
    assert [r.name for r in _since(t0)] == ["test.raises"]


def test_recorder_is_bounded_and_counts_what_it_drops():
    rec = Recorder(capacity=4)
    for k in range(6):
        rec.add("test.r", float(k), k + 0.5, {})
    assert len(rec.records) == 4
    assert rec.dropped_until == 1.5
    assert [r.t0 for r in rec.inside(2.0, 10.0)] == [2.0, 3.0, 4.0, 5.0]
    assert [r.t0 for r in rec.inside(3.0, 4.5)] == [3.0, 4.0]
    assert rec.inside(1.0, 10.0) is None        # a dropped record lay there
    assert spans.CAPACITY >= 1 << 17            # a 40 s open-loop window


def test_kept_records_leave_the_garbage_collector():
    """A kept record the collector still tracked would lengthen every
    full collection: in the open loop, a stall of the served path."""
    with span("test.kept", calls=1) as s:
        s.attrs["bytes"] = 64
    with span("test.kept"):
        pass
    gc.collect()
    gc.collect()
    assert not any(gc.is_tracked(r) for r in list(RECORDER.records)[-2:])


def test_fresh_jit_adds_a_compile_record_under_the_open_span():
    t0 = time.perf_counter()
    with span("test.compiling"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    recs = _since(t0)
    (outer,) = [r for r in recs if r.name == "test.compiling"]
    compiles = [r for r in recs if r.name == COMPILE]
    assert compiles
    assert outer.t0 <= compiles[-1].t0 <= compiles[-1].t1 <= outer.t1


def test_program_span_names_are_not_the_benchmarks():
    spec = importlib.util.spec_from_file_location(
        "trace_reduce", ROOT / "chipbench" / "trace_reduce.py")
    trace_reduce = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_reduce)
    names = _program_span_names()
    assert set(SERVE_SPANS) | {"fleet.surface"} <= names
    assert not names & set(trace_reduce.SPAN_NAMES)


# ---------------------------------------------------------------------------
# the served and fleet paths
# ---------------------------------------------------------------------------
def test_service_records_serve_spans_in_order(quick_vampire):
    trs = [idd_loops.validation_sweep(n) for n in (1, 8, 16)]
    svc = EstimationService(quick_vampire, ServiceConfig())
    t0 = time.perf_counter()
    tickets, _ = svc.submit_many(trs)
    assert svc.drain() == len(trs)
    recs = [r for r in _since(t0) if r.name != COMPILE]
    assert [r.name for r in recs] == SERVE_SPANS
    by = {r.name: r for r in recs}
    repad = by["ring.repad"].attrs
    lengths = [int(tr.n) for tr in trs]
    # three traces within the largest length bucket: one row each
    assert by["trace.segment"].attrs == {"segments": 3, "long_traces": 0}
    assert by["service.combine"].attrs == {"rows": 3}
    assert repad["n_real"] == 3 and repad["rows"] == 3
    assert repad["real_cmds"] == sum(lengths)
    assert repad["slot_cmds"] == 8 * 256              # count x length bucket
    assert repad["wait_s"] >= 0
    # cmd, bank, row, col, dt (int32), the 16-word line, the f32 weight;
    # one-row traces carry nothing: their empty carries stay on the device
    assert by["ring.transfer"].attrs["bytes"] == 8 * 256 * (5 * 4 + 64 + 4)
    # the lint's cmd, bank and dt planes (int32), the rows and their
    # length padded to a power of 2 (4 rows); whole traces' empty carries
    # stay on the device
    assert by["lint.rules"].attrs["bytes"] == 4 * 3 * 4 * (
        1 << max(max(lengths) - 1, 1).bit_length())
    # nothing fired: only the four rows' int32 counts came down
    assert by["lint.fetch"].attrs == {"bytes": 4 * 4, "fired_traces": 0}
    # the report: one float per (bucket slot, vendor) for each leaf
    assert by["service.slice"].attrs["bytes"] > 0
    assert by["service.slice"].attrs["bytes"] % (8 * 4) == 0
    for t in tickets:
        svc.result(t)


def test_service_metrics_are_bounded_and_over_wall_time(quick_vampire):
    from repro.serving.service import RECENT
    svc = EstimationService(quick_vampire, ServiceConfig())
    t0 = time.perf_counter()
    svc.submit_many([idd_loops.validation_sweep(n) for n in (1, 8)])
    svc.drain()
    wall = time.perf_counter() - t0
    m = svc.metrics()
    assert m.completed == 2 and m.traces_per_s >= 2 / wall
    for samples in (svc._fills, svc._dispatch_s, svc._latency_s):
        assert samples.maxlen == RECENT


@pytest.mark.parametrize("length_bucket,gather_free", [(16384, 1),
                                                       (1 << 15, 0)])
def test_engine_dispatch_counts_gather_free_rows(quick_vampire,
                                                 length_bucket, gather_free):
    """``engine.dispatch`` says whether the bucket's row length reads
    earlier commands without gathers: at the ring's 16384, yes; past
    ``energy_model.gather_free``'s gate, no."""
    from repro.serving.ring import RingConfig
    svc = EstimationService(quick_vampire, ServiceConfig(
        ring=RingConfig(length_buckets=(256, length_bucket))))
    trace = idd_loops.validation_sweep(1000)      # 8016 commands
    t0 = time.perf_counter()
    (ticket,), _ = svc.submit_many([trace])
    assert svc.drain() == 1
    by = {r.name: r for r in _since(t0)}
    assert by["ring.repad"].attrs["slot_cmds"] == 8 * length_bucket
    assert by["engine.dispatch"].attrs == {"gather_free": gather_free}
    svc.result(ticket)


def test_service_keeps_only_recent_rejections_but_counts_all(
        quick_vampire, monkeypatch):
    from repro.serving import service
    from repro.serving.ring import RingConfig
    monkeypatch.setattr(service, "RECENT", 2)
    svc = EstimationService(quick_vampire, ServiceConfig(
        ring=RingConfig(length_buckets=(8,), count_buckets=(8,)),
        lint=False))
    # 80 commands: past a whole window of 8 rows of 8
    _, rejections = svc.submit_many([idd_loops.validation_sweep(8)] * 3)
    assert [r.reason for r in rejections] == ["too-long"] * 3
    assert svc.rejections == tuple(rejections[1:])
    assert svc.metrics().rejected == 3


def test_fleet_surface_records_its_span(tiny_fleet):
    trace, weight = batch_traces([(idd_loops.validation_sweep(8), 0)])
    t0 = time.perf_counter()
    fleet.fleet_surface_energy(list(tiny_fleet), trace, weight)
    recs = [r for r in _since(t0) if r.name == "fleet.surface"]
    assert len(recs) == 1


def test_program_spans_land_on_the_profilers_host_plane(tmp_path):
    from repro.analysis import trace_lint
    trace = idd_loops.validation_sweep(4)
    trace_lint.lint_traces([trace])                 # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        trace_lint.lint_traces([trace])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    planes = jax.profiler.ProfileData.from_file(path).planes
    host = {e.name for p in planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events}
    assert {"lint.pack", "lint.rules", "lint.fetch", "lint.extract"} <= host


def test_lint_records_every_span_whether_or_not_a_trace_fired():
    from repro.analysis import trace_lint
    from repro.core.dram import RD, make_trace
    clean = idd_loops.validation_sweep(4)
    bad = make_trace([RD], [0])                     # RD to a closed bank
    for trs, fired in (([clean, clean], 0), ([clean, bad], 1)):
        t0 = time.perf_counter()
        diags = trace_lint.lint_traces(trs)
        recs = [r for r in _since(t0) if r.name != COMPILE]
        assert [r.name for r in recs] == ["lint.pack", "lint.rules",
                                          "lint.fetch", "lint.extract"]
        fetch = recs[2].attrs
        assert fetch["fired_traces"] == fired == len(diags)
        # the two int32 counts, then one fired row's planes and carry up
        # and its (R, N) bool mask, int32 deficits and int32 banks down
        carry = 4 * trace_lint.CARRY_WIDTH
        n = recs[1].attrs["bytes"] // (3 * 4 * 2)
        assert fetch["bytes"] == 2 * 4 + fired * (
            3 * 4 * n + carry + len(trace_lint.RULES) * n * 9)


# ---------------------------------------------------------------------------
# stable names
# ---------------------------------------------------------------------------
def _named(jaxpr, primitive: str) -> set[str]:
    """The ``name`` of every ``primitive`` equation, nested ones too."""
    out = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            out.add(str(eqn.params["name"]))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out |= _named(inner, primitive)
    return out


def _small_batch(t: int = 8, n: int = 256):
    z = jnp.zeros((t, n), jnp.int32)
    return (CommandTrace(z, z, z, z, jnp.zeros((t, n, 16), jnp.uint32), z),
            jnp.ones((t, n), jnp.float32))


@pytest.mark.parametrize("surface", (False, True), ids=("mean", "surface"))
def test_vampire_kernels_carry_their_names(tiny_fleet, surface):
    from repro.kernels.vampire_energy import ops as vops
    trace, weight = _small_batch()
    stacked = fleet.stack_params([m.params for m in tiny_fleet])
    jaxpr = jax.make_jaxpr(lambda t, w, s: vops.batched_charge_matrix(
        t, w, s, surface=surface, interpret=True))(trace, weight, stacked)
    kernel = "vampire_surface" if surface else "vampire_energy"
    assert _named(jaxpr.jaxpr, "pallas_call") == {"vampire_features",
                                                  kernel}
    assert "_vampire_charge_matrix" in _named(jaxpr.jaxpr, "jit")


@pytest.mark.parametrize("kind", ("micron", "drampower"))
@pytest.mark.parametrize("surface", (False, True), ids=("mean", "surface"))
def test_baseline_kernels_carry_their_names(kind, surface):
    from repro.core.baselines_power import BASELINE_IDD_KEYS
    from repro.kernels.baseline_energy import ops as bops
    trace, weight = _small_batch()
    table = jnp.ones((3, len(BASELINE_IDD_KEYS)), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda t, w, tb: bops.baseline_charge_matrix(
        t, w, tb, kind, surface=surface, interpret=True))(trace, weight,
                                                          table)
    suffix = "surface" if surface else "energy"
    assert _named(jaxpr.jaxpr, "pallas_call") == {f"{kind}_{suffix}"}
    assert "_baseline_charge_matrix" in _named(jaxpr.jaxpr, "jit")


def test_lint_and_serve_programs_carry_their_names(quick_vampire):
    from repro.analysis import trace_lint
    z = jnp.zeros((8, 256), jnp.int32)
    c = jnp.zeros((8, trace_lint.CARRY_WIDTH), jnp.int32)
    lint_count, lint_rules = trace_lint._programs()
    assert "jit_lint_count" in lint_count.lower(z, z, z, c).as_text()
    assert "jit_lint_rules" in lint_rules.lower(z, z, z, c).as_text()
    svc = EstimationService(quick_vampire, ServiceConfig())
    svc.submit_many([idd_loops.validation_sweep(1)])
    svc.drain()
    assert [fn.__name__ for fn in svc.engine._fns.values()] == \
        ["serve_estimate"]

