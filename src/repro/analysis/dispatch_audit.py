"""Compile-time dispatch auditor: prove every registered (estimator kind x
impl x mode) combination is jit-clean WITHOUT running the integrator.

For each combination the auditor traces the ``estimate`` dispatch to a
jaxpr and lowers it to StableHLO text (the same artifact
``launch/hlo_analysis.py`` mines for cost totals), then checks:

* **float64 promotion** — no ``f64``/``c128`` buffers anywhere in the
  lowered module: the energy pipeline is a float32 contract end to end,
  and a stray Python float in the wrong place silently doubles every
  buffer;
* **host callbacks** — no ``pure_callback`` / ``io_callback`` / debug
  primitives inside the traced dispatch: a host round-trip per call would
  serialize the batched engine;
* **pad-row masking** — the :class:`~repro.core.estimate_batch.TraceBatch`
  validity ``weight`` must survive dead-code elimination, i.e. the
  result really depends on the mask (a dispatch that drops it bills
  padding rows);
* **recompilation hazards** — repeated calls, same-shape re-pads of a
  different ragged trace set, and equal-size vendor subsets must hit the
  jit cache of the shared batched dispatchers (``_cache_size`` growth
  probes, generalizing the PR 3 regression test into a pass); the
  serving stack gets its own probe (:func:`audit_serving`) asserting the
  ring's pad-shape bucketing bounds the engine's compiled-program count,
  traces cut into ring rows with their carries included.

Findings are structured (:class:`AuditFinding`); ``python -m
repro.analysis`` fails the CI gate on any ERROR severity.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Iterable, Sequence

import numpy as np

ERROR = "error"
WARNING = "warning"

#: substrings of primitive names that imply a host round-trip
_CALLBACK_MARKERS = ("callback", "outside_call", "infeed", "outfeed",
                    "debug_print")

# HLO spells the dtype inside the shape ("tensor<4xf64>"), so a plain \b
# never fires after the 'x' — accept either a word boundary or that 'x'.
_F64_RE = re.compile(r"(?:\b|x)(?:f64|c128)\b")

#: impls whose batched dispatch consumes the padded batch directly and must
#: therefore consume the validity mask (the reference oracle instead slices
#: per ragged trace, where a dt=0 NOP pad row is exact by construction)
_MASKED_IMPLS = ("vectorized", "pallas")

_MODES = ("mean", "range", "distribution", "surface")


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    """One dispatch-audit diagnostic."""
    kind: str       # estimator kind ('vampire' | 'micron' | 'drampower')
    impl: str       # registry impl name
    mode: str       # estimate mode
    check: str      # 'float64' | 'host_callback' | 'pad_masking' |
                    # 'recompile' | 'audit_trace' | 'lint'
    severity: str   # 'error' | 'warning'
    detail: str

    def __str__(self):  # pragma: no cover - formatting
        return (f"[{self.severity.upper()}] {self.check}: "
                f"kind={self.kind} impl={self.impl} mode={self.mode} — "
                f"{self.detail}")


def errors_of(findings: Iterable[AuditFinding]) -> list[AuditFinding]:
    return [f for f in findings if f.severity == ERROR]


# ---------------------------------------------------------------------------
# Shared probe inputs
# ---------------------------------------------------------------------------
def default_audit_batch():
    """A small heterogeneous TraceBatch (real padding rows present, so the
    pad-masking check is not vacuous)."""
    from repro.core import idd_loops, traces
    from repro.core.estimate_batch import TraceBatch
    trs = [idd_loops.idd0(reps=4),
           idd_loops.idd4r(reps=2),
           traces.app_trace(traces.SPEC_APPS[0], n_requests=24)]
    return TraceBatch.from_traces(trs)


def _estimate_fn(model, impl: str, mode: str) -> Callable:
    """The (trace, weight) -> report function the audit traces: exactly the
    production dispatch, model params closed over as constants."""
    from repro.core.estimate_batch import TraceBatch

    def fn(trace, weight):
        kwargs = {}
        if mode == "distribution":
            kwargs = dict(ones_frac=0.5, toggle_frac=0.25)
        return model.estimate(TraceBatch(trace, weight), mode=mode,
                              impl=impl, **kwargs)
    return fn


# ---------------------------------------------------------------------------
# jaxpr helpers
# ---------------------------------------------------------------------------
def _iter_jaxprs(jaxpr):
    """The jaxpr and every sub-jaxpr reachable through equation params
    (pjit bodies, scan/while carries, cond branches, pallas kernels)."""
    import jax.extend as jex  # noqa: F401  (presence varies by version)
    seen = []
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        seen.append(j)
        for eqn in j.eqns:
            for val in eqn.params.values():
                for sub in _as_jaxprs(val):
                    stack.append(sub)
    return seen


def _as_jaxprs(val):
    out = []
    vals = val if isinstance(val, (list, tuple)) else (val,)
    for v in vals:
        inner = getattr(v, "jaxpr", None)
        if inner is not None and hasattr(inner, "eqns"):
            out.append(inner)          # ClosedJaxpr
        elif hasattr(v, "eqns") and hasattr(v, "invars"):
            out.append(v)              # raw Jaxpr
    return out


def _primitive_names(jaxpr) -> set[str]:
    return {eqn.primitive.name for j in _iter_jaxprs(jaxpr)
            for eqn in j.eqns}


def _dce_used_invars(jaxpr) -> list[bool] | None:
    """Which top-level invars survive DCE (None when the partial-eval API
    is unavailable in this jax version — callers then skip the check
    rather than report a false positive)."""
    try:
        from jax._src.interpreters import partial_eval as pe
        _, used = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        return list(used)
    except Exception:
        return None


# ---------------------------------------------------------------------------
# The per-combination audit
# ---------------------------------------------------------------------------
def audit_combination(model, impl: str, mode: str,
                      tb=None) -> list[AuditFinding]:
    """Trace + lower one (kind, impl, mode) dispatch and run the static
    checks. Returns findings (empty when clean)."""
    import jax

    if tb is None:
        tb = default_audit_batch()
    kind = model.kind
    fn = _estimate_fn(model, impl, mode)
    findings: list[AuditFinding] = []

    try:
        closed = jax.make_jaxpr(fn)(tb.trace, tb.weight)
    except Exception as exc:  # infra failure, not a verified dispatch bug
        return [AuditFinding(kind, impl, mode, "audit_trace", ERROR,
                             f"dispatch failed to trace: {exc!r}")]

    prims = _primitive_names(closed.jaxpr)
    hits = sorted(p for p in prims
                  if any(m in p for m in _CALLBACK_MARKERS))
    if hits:
        findings.append(AuditFinding(
            kind, impl, mode, "host_callback", ERROR,
            f"host-callback primitives in traced dispatch: {hits}"))

    if impl in _MASKED_IMPLS:
        used = _dce_used_invars(closed.jaxpr)
        if used is not None and not used[-1]:  # weight flattens last
            findings.append(AuditFinding(
                kind, impl, mode, "pad_masking", ERROR,
                "the TraceBatch validity weight is dead code: padding "
                "rows would be billed as real commands"))

    try:
        text = jax.jit(fn).lower(tb.trace, tb.weight).as_text()
    except Exception as exc:
        findings.append(AuditFinding(
            kind, impl, mode, "audit_trace", WARNING,
            f"dispatch traced but failed to lower: {exc!r}"))
        return findings

    m = _F64_RE.search(text)
    if m:
        findings.append(AuditFinding(
            kind, impl, mode, "float64", ERROR,
            f"lowered HLO contains {m.group(0)} buffers (float32 contract "
            f"violated)"))
    return findings


# ---------------------------------------------------------------------------
# Recompilation-hazard probes (vectorized impl: the @jax.jit dispatchers)
# ---------------------------------------------------------------------------
def _mode_dispatcher(mode: str):
    from repro.core import estimate_batch as EB
    # surface's jitted core is the shared chunk-charge program (the public
    # wrapper is a plain function so the chunked dispatch can reuse it)
    return {"mean": EB.batched_reports,
            "range": EB.batched_range_reports,
            "distribution": EB.batched_distribution_reports,
            "surface": EB._surface_chunk_charge}[mode]


def audit_recompilation(model, modes: Sequence[str] = _MODES,
                        tb=None, tb_same_shape=None) -> list[AuditFinding]:
    """Drive the production ``estimate`` path and assert the shared jitted
    dispatchers stop compiling once warm: repeated calls, a same-shape
    re-pad of a DIFFERENT ragged trace set, and equal-size vendor subsets
    must all hit the cache."""
    if tb is None:
        tb = default_audit_batch()
    if tb_same_shape is None:
        from repro.core import dram
        from repro.core.estimate_batch import TraceBatch
        # different ragged content, identical padded shape
        perm = list(range(tb.n_traces))[::-1]
        import jax
        trace = jax.tree_util.tree_map(lambda x: x[np.asarray(perm)],
                                       tb.trace)
        tb_same_shape = TraceBatch(trace, tb.weight[np.asarray(perm)])
    kind = model.kind
    vendors = list(model.vendors)
    findings: list[AuditFinding] = []

    for mode in modes:
        fn = _mode_dispatcher(mode)
        kwargs = ({"ones_frac": 0.5, "toggle_frac": 0.25}
                  if mode == "distribution" else {})

        def call(batch, vs):
            model.estimate(batch, vs, mode=mode, impl="vectorized",
                           **kwargs)

        call(tb, vendors)                       # warm
        base = fn._cache_size()
        call(tb, vendors)                       # repeat: must hit
        if fn._cache_size() != base:
            findings.append(AuditFinding(
                kind, "vectorized", mode, "recompile", ERROR,
                "repeated estimate over an identical TraceBatch "
                "recompiled the batched dispatcher"))
        call(tb_same_shape, vendors)            # same shape, new content
        if fn._cache_size() != base:
            findings.append(AuditFinding(
                kind, "vectorized", mode, "recompile", ERROR,
                "a same-shape re-pad of a different ragged trace set "
                "recompiled the batched dispatcher"))
        if len(vendors) >= 3:
            call(tb, vendors[:2])               # first subset of size 2
            grown = fn._cache_size()
            if grown > base + 1:
                findings.append(AuditFinding(
                    kind, "vectorized", mode, "recompile", ERROR,
                    "a vendor subset compiled more than one new program"))
            call(tb, vendors[1:])               # same-size subset: must hit
            if fn._cache_size() != grown:
                findings.append(AuditFinding(
                    kind, "vectorized", mode, "recompile", ERROR,
                    "an equal-size vendor subset recompiled the batched "
                    "dispatcher (subset slicing is shape-unstable)"))
    return findings


# ---------------------------------------------------------------------------
# Serving-path recompile probe (the ring's bucketing contract)
# ---------------------------------------------------------------------------
def audit_serving(model, impl: str = "vectorized") -> list[AuditFinding]:
    """Drive the serving stack's dispatch path and assert the ring's
    pad-shape bucketing bounds the engine's compiled-program count:
    arrival mixes that vary WITHIN one (count, length) bucket must hit
    the cache, and crossing into a new bucket compiles exactly one new
    program.  This is the serving twin of :func:`audit_recompilation` —
    it guards the property that made ``serve.power_report``'s
    exact-request-shape re-pads a bug."""
    from repro.core import idd_loops
    from repro.serving import EstimationService, RingConfig, ServiceConfig

    kind = model.kind
    findings: list[AuditFinding] = []
    short = [idd_loops.idd0(reps=2), idd_loops.idd0(reps=3),
             idd_loops.idd4r(reps=2)]
    long = idd_loops.validation_sweep(64)
    b1 = 1 << (max(int(tr.n) for tr in short) - 1).bit_length()
    b2 = max(1 << (int(long.n) - 1).bit_length(), b1 * 2)
    svc = EstimationService(model, ServiceConfig(
        ring=RingConfig(length_buckets=(b1, b2), count_buckets=(4, 8)),
        impl=impl, lint=False))

    def run(traces):
        svc.submit_many(traces)
        svc.drain()
        return svc.engine.cache_size()

    base = run(short)                          # warm: one (4, b1) program
    if run(short[:2]) != base or run(short + short[:1]) != base:
        findings.append(AuditFinding(
            kind, impl, "mean", "recompile", ERROR,
            "varying arrival mixes within one (count, length) bucket "
            "recompiled the serving dispatch (ring bucketing broken)"))
    crossed = run([long])                      # new length bucket: (4, b2)
    if crossed > base + 1:
        findings.append(AuditFinding(
            kind, impl, "mean", "recompile", ERROR,
            "crossing one length bucket compiled more than one new "
            "serving program"))
    if run([long] + short[:1]) != crossed:     # mixed window, known bucket
        findings.append(AuditFinding(
            kind, impl, "mean", "recompile", ERROR,
            "a mixed-length window landing in an already-compiled bucket "
            "recompiled the serving dispatch"))
    return findings + _audit_segmented(model, impl)


def _audit_segmented(model, impl: str,
                     segments: Sequence[int] = (2, 17, 64)
                     ) -> list[AuditFinding]:
    """Traces past the largest length bucket, cut into 2, 17 and 64 ring
    rows with their carries, linted and served: the compiled serving and
    lint programs stay within the bucket vocabulary (count buckets x
    length buckets; for the lint, powers of two up to the largest count
    and length buckets) and a second pass of the same traces compiles
    nothing; the carried program holds the float32 contract and makes no
    host callback."""
    import jax

    from repro.analysis import trace_lint
    from repro.core import idd_loops
    from repro.core.dram import CommandTrace
    from repro.serving import EstimationService, RingConfig, ServiceConfig

    kind = model.kind
    findings: list[AuditFinding] = []
    ring = RingConfig(length_buckets=(64, 128), count_buckets=(4, 8, 32, 64))
    svc = EstimationService(model, ServiceConfig(ring=ring, impl=impl))
    unit = idd_loops.idd0(reps=4)
    reps = -(-max(segments) * ring.max_length // int(unit.n))
    stream = CommandTrace(*(np.concatenate([np.asarray(x)] * reps)
                            for x in unit))
    trs = [CommandTrace(*(x[:k * ring.max_length - 7] for x in stream))
           for k in segments]
    lint_count = trace_lint._programs()[0]

    def run():
        tickets, rejections = svc.submit_many(trs)
        svc.drain()
        for t in tickets:
            if t is not None:
                svc.result(t)
        return (svc.engine.cache_size(), lint_count._cache_size(),
                rejections)

    lint_before = lint_count._cache_size()
    first, lint_first, rejections = run()
    if rejections:
        findings.append(AuditFinding(
            kind, impl, "mean", "lint", ERROR,
            f"the lint rejected {len(rejections)} legal segmented traces: "
            f"{[r.rules for r in rejections]}"))
    vocabulary = len(ring.count_buckets) * len(ring.length_buckets)
    lint_vocabulary = (ring.max_batch.bit_length()
                       * (ring.max_length - 1).bit_length())
    if first > vocabulary:
        findings.append(AuditFinding(
            kind, impl, "mean", "recompile", ERROR,
            f"segmented traces compiled {first} serving programs, past "
            f"the {vocabulary} of the bucket vocabulary"))
    if lint_first - lint_before > lint_vocabulary:
        findings.append(AuditFinding(
            kind, impl, "mean", "recompile", ERROR,
            f"segmented traces compiled {lint_first - lint_before} lint "
            f"programs, past the {lint_vocabulary} of the vocabulary"))
    if run()[:2] != (first, lint_first):
        findings.append(AuditFinding(
            kind, impl, "mean", "recompile", ERROR,
            "a second pass of the same segmented traces recompiled the "
            "serving or the lint dispatch"))

    svc.submit_many(trs[-1:])
    rb = svc.ring.take()
    fn = svc.engine._dispatch_fn(None, False)
    args = (svc.engine.resident, rb.batch.trace, rb.batch.weight,
            rb.batch.carry)
    prims = _primitive_names(jax.make_jaxpr(fn)(*args).jaxpr)
    hits = sorted(p for p in prims
                  if any(m in p for m in _CALLBACK_MARKERS))
    if hits:
        findings.append(AuditFinding(
            kind, impl, "mean", "host_callback", ERROR,
            f"host-callback primitives in the carried dispatch: {hits}"))
    m = _F64_RE.search(fn.lower(*args).as_text())
    if m:
        findings.append(AuditFinding(
            kind, impl, "mean", "float64", ERROR,
            f"the carried dispatch lowers {m.group(0)} buffers (float32 "
            f"contract violated)"))
    return findings


# ---------------------------------------------------------------------------
# Fleet-scale chunked-dispatch probe (the zero-restack scaling contract)
# ---------------------------------------------------------------------------
def audit_fleet_chunked(tb=None, module_chunk: int = 4
                        ) -> list[AuditFinding]:
    """Drive the fleet-scale chunked surface dispatch and assert its
    scaling contract: the compiled-program count of the chunk charge
    program depends on the chunk SIZE, never the chunk COUNT — growing
    the fleet at a fixed chunk size must reuse the warm program (the
    property that makes a 50k-module surface map cost one compile), and
    the donated scatter carry must stay float32 (a stray f64 in the
    accumulator doubles the one buffer the chunked path exists to
    bound)."""
    import jax
    import jax.numpy as jnp

    from repro.core import device_sim
    from repro.core import estimate_batch as EB
    from repro.core.dram import N_BANKS, N_ROW_BANDS

    if tb is None:
        tb = default_audit_batch()
    findings: list[AuditFinding] = []

    _, small = device_sim.synth_fleet_params(2 * module_chunk)
    _, big = device_sim.synth_fleet_params(4 * module_chunk)
    EB.chunked_surface_reports(tb.trace, tb.weight, small,
                               module_chunk=module_chunk)        # warm
    base = EB._surface_chunk_charge._cache_size()
    EB.chunked_surface_reports(tb.trace, tb.weight, big,
                               module_chunk=module_chunk)        # 2x chunks
    if EB._surface_chunk_charge._cache_size() != base:
        findings.append(AuditFinding(
            "fleet", "vectorized", "surface", "recompile", ERROR,
            "growing the fleet at a fixed module_chunk recompiled the "
            "chunk charge program (compiled-program count must depend on "
            "chunk size, not chunk count)"))
    EB.chunked_surface_reports(tb.trace, tb.weight, small,
                               module_chunk=module_chunk)        # revisit
    if EB._surface_chunk_charge._cache_size() != base:
        findings.append(AuditFinding(
            "fleet", "vectorized", "surface", "recompile", ERROR,
            "revisiting an already-seen fleet size recompiled the chunk "
            "charge program"))

    # float64 promotion in the donated scatter carry
    t = tb.trace.cmd.shape[0]
    acc = jnp.zeros((t, 2 * module_chunk, N_BANKS, N_ROW_BANDS),
                    jnp.float32)
    charge = jnp.zeros((t, module_chunk, N_BANKS, N_ROW_BANDS),
                       jnp.float32)
    try:
        text = EB._scatter_chunk.lower(acc, charge, jnp.int32(0),
                                       jnp.int32(0)).as_text()
    except Exception as exc:
        findings.append(AuditFinding(
            "fleet", "vectorized", "surface", "audit_trace", WARNING,
            f"chunk scatter failed to lower: {exc!r}"))
        return findings
    m = _F64_RE.search(text)
    if m:
        findings.append(AuditFinding(
            "fleet", "vectorized", "surface", "float64", ERROR,
            f"the donated chunk-scatter carry lowers with {m.group(0)} "
            f"buffers (float32 contract violated)"))
    return findings


# ---------------------------------------------------------------------------
# Online-recalibration probe (the fit-while-serving contract)
# ---------------------------------------------------------------------------
def audit_recalibration(model=None) -> list[AuditFinding]:
    """Audit the streaming-fit path (``repro.core.recalibrate``):

    * the ONE incremental update step (``_update_stats``) lowers f64-free
      (the sufficient statistics are a float32 pytree end to end);
    * a round-robin telemetry stream — fixed slice width, moving cell
      window, advancing tick — compiles the update step exactly ONCE;
    * a streaming refit pushed through ``ServingEngine.update_model`` is
      treedef-stable: the warm engine re-dispatches with ZERO new
      compiled programs (the property that makes fit-while-serving free).
    """
    import jax.numpy as jnp

    from repro.core import params as P
    from repro.core import recalibrate
    from repro.serving.engine import ServingEngine

    if model is None:
        from repro.core import vampire as V
        model = V.reference_vampire()
    cfg = recalibrate.RecalConfig(probe_reps=64, n_rows=8,
                                  probe_modules=2, slice_size=32)
    specs = [P.ModuleSpec(v, i, 2015)
             for v in model.vendors for i in range(2)]
    fitter = recalibrate.StreamingFitter(model, specs, cfg)
    findings: list[AuditFinding] = []

    # ---- float64 promotion in the lowered update step --------------------
    cur = jnp.zeros((len(specs), cfg.slice_size), jnp.float32)
    idx = jnp.arange(cfg.slice_size, dtype=jnp.int32)
    try:
        text = recalibrate._update_stats.lower(
            fitter.stats, cur, idx, fitter._decay, fitter._predicted,
            fitter._floor).as_text()
    except Exception as exc:
        findings.append(AuditFinding(
            "recalibrate", "streaming", "fit", "audit_trace", WARNING,
            f"incremental update step failed to lower: {exc!r}"))
    else:
        m = _F64_RE.search(text)
        if m:
            findings.append(AuditFinding(
                "recalibrate", "streaming", "fit", "float64", ERROR,
                f"the incremental update step lowers with {m.group(0)} "
                f"buffers (the sufficient statistics must stay float32)"))

    # ---- one compiled program across the telemetry stream ----------------
    n_cells = fitter.n_cells
    before = recalibrate._update_stats._cache_size()
    fitter.observe(np.asarray(fitter._predicted[:, :cfg.slice_size]),
                   np.arange(cfg.slice_size), tick=1)        # warm
    base = recalibrate._update_stats._cache_size()
    if base > before + 1:
        findings.append(AuditFinding(
            "recalibrate", "streaming", "fit", "recompile", ERROR,
            "the first telemetry slice compiled more than one update "
            "program"))
    shifted = (np.arange(cfg.slice_size) + cfg.slice_size) % n_cells
    fitter.observe(
        np.asarray(fitter._predicted)[:, shifted], shifted, tick=2)
    if recalibrate._update_stats._cache_size() != base:
        findings.append(AuditFinding(
            "recalibrate", "streaming", "fit", "recompile", ERROR,
            "the round-robin telemetry stream recompiled the update step "
            "(a fixed-width slice at a new tick must hit the cache)"))

    # ---- streaming refit -> update_model: zero new programs --------------
    engine = ServingEngine(model)
    tb = default_audit_batch()
    engine.dispatch(tb)                                      # warm
    warm = engine.cache_size()
    engine.update_model(fitter.refit())
    engine.dispatch(tb)
    if engine.cache_size() != warm:
        findings.append(AuditFinding(
            "recalibrate", "streaming", "fit", "recompile", ERROR,
            "a streaming refit pushed through ServingEngine.update_model "
            "compiled new programs (the refresh is not treedef-stable)"))
    return findings


# ---------------------------------------------------------------------------
# Whole-registry sweep
# ---------------------------------------------------------------------------
def audit_model(model, impls: Sequence[str] | None = None,
                modes: Sequence[str] | None = None,
                tb=None, recompile: bool = True) -> list[AuditFinding]:
    """Audit every (impl x mode) dispatch of one estimator."""
    from repro.core import model_api
    if tb is None:
        tb = default_audit_batch()
    findings: list[AuditFinding] = []
    for impl in (impls if impls is not None else model_api.registered_impls()):
        for mode in (modes if modes is not None else
                     model_api.resolve_impl(impl).modes):
            findings.extend(audit_combination(model, impl, mode, tb))
    if recompile:
        findings.extend(audit_recompilation(
            model, modes if modes is not None else _MODES, tb))
    return findings


def audit_all(vampire, kinds: Sequence[str] | None = None,
              **kwargs) -> list[AuditFinding]:
    """Audit every registered estimator kind built from one fitted model."""
    from repro.core import model_api
    findings: list[AuditFinding] = []
    for kind in (kinds if kinds is not None else model_api.ESTIMATOR_KINDS):
        model = model_api.make_estimator(kind, vampire)
        findings.extend(audit_model(model, **kwargs))
    return findings
