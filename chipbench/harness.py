"""One run of one cell of the chip benchmark.

Everything that belongs to one cell is found by name in data:
``BENCHMARK.json`` names the cell's configuration and traffic; the
configuration's file (``configs/<config>.json``) names the system module
(``systems/<system>.py``) that builds what is served; the traffic's file
(``traffic/<traffic>.json``) names the loop module (``loops/<loop>.py``)
that drives it; each metric is read by ``metrics/<metric>.py``; the
limits of the output check are in ``limits/<workload>.json``.

A run: check the device, build the system and warm every shape the
cell's seeded traffic uses (set-up), measure for ``--seconds`` (with the
profiler on under ``--trace 1``), read the device's peak memory, then
compare a seeded sample of the answers with the plain reference.  The
last line of standard output is one JSON object; the numbers compared,
each with its limit, are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import glob
import importlib
import importlib.util
import json
import os
import pathlib
import resource
import shutil
import sys
import time

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: fixed paths inside the checkout: JAX's persistent compilation cache,
#: fitted models, profiles (all made at run time, listed in .gitignore)
CACHE_DIR = BENCH_DIR / ".cache"
JAX_CACHE = CACHE_DIR / "jax"
PROFILE_DIR = CACHE_DIR / "profile"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator the cell can run on."""


class CompileLog:
    """Counts XLA backend compiles and persistent-cache hits through
    ``jax.monitoring`` (copied from the program's ``chip_smoke.py``)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT:
            self.cache_hits += 1


class HostLog:
    """What the host did while the window ran: garbage collections per
    generation (count, seconds), the process's CPU seconds, context
    switches and page faults.  Printed on an earlier line, so that a run
    that did less work in its window shows whether the host held it."""

    def __init__(self):
        self.gc = {g: [0, 0.0] for g in range(3)}
        self._t = 0.0
        self._ru = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            rec = self.gc[info["generation"]]
            rec[0] += 1
            rec[1] += time.perf_counter() - self._t

    def start(self) -> None:
        self._ru = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._on_gc)

    def stop(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        out = {f"gc{g}": [n, round(s, 4)] for g, (n, s) in self.gc.items()}
        for f in ("ru_utime", "ru_stime"):
            out[f[3:]] = round(getattr(ru, f) - getattr(self._ru, f), 3)
        for f in ("ru_nvcsw", "ru_nivcsw", "ru_minflt", "ru_majflt"):
            out[f[3:]] = getattr(ru, f) - getattr(self._ru, f)
        return out


def span_summary(records: list, w0: float, w1: float) -> dict:
    """Per span name inside ``[w0, w1]``: count, median and longest
    (ms)."""
    by: dict = {}
    for n, t0, t1 in records:
        if t0 >= w0 and t1 <= w1 and n != "window":
            by.setdefault(n, []).append((t1 - t0) * 1e3)
    return {n: [len(d), round(float(np.median(d)), 3), round(max(d), 3)]
            for n, d in by.items()}


class Spans:
    """Host spans around the calls into the program: kept in memory on
    the host clock, and written into the profiler's trace (same clock as
    the device) while a trace is being taken."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))


@dataclasses.dataclass
class Outcome:
    """What a loop's measured window did."""
    window_s: float
    attempted: int
    failed: int
    counters: dict
    latencies_s: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    setup_s: float
    outcome: Outcome
    spans: list
    trace: dict | None
    peaks: dict


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, kind: str, workload: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries the cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", (workload,))]


def read_metric(name: str, run: Run):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name}").read(run)


def device_info(chips: int, peaks: dict) -> dict:
    """The devices JAX found, checked against the cell: TPUs, at least
    ``chips`` of them, of a kind with peaks in ``peaks.json``."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found {d.platform!r} devices, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    if d.device_kind not in peaks:
        raise NoChip(f"device kind {d.device_kind!r} has no peaks in "
                     f"peaks.json")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def enable_caches() -> None:
    """JAX's persistent compilation cache at its fixed path in the
    checkout, caching every program however fast it compiled, so that
    only a cell's first run in a checkout compiles."""
    import jax
    JAX_CACHE.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE)
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def prepare(workload: str, seed: int, *, bench: dict | None = None,
            config: dict | None = None, traffic: dict | None = None):
    """Build the cell's system and its loop's driver (set-up, warm-up
    included).  ``bench``/``config``/``traffic`` default to the files; a
    test passes smaller ones."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cell = cell_of(bench, workload)
    if config is None:
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        config = load_json(ROOT / entry["file"])
    if traffic is None:
        traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    spans = Spans()
    seed = int(seed) % (1 << 64)
    t0 = time.perf_counter()
    system = importlib.import_module(
        f"chipbench.systems.{config['system']}").build(config, CACHE_DIR)
    t1 = time.perf_counter()
    driver = importlib.import_module(
        f"chipbench.loops.{traffic['loop']}").Driver(system, traffic, seed,
                                                      spans)
    t2 = time.perf_counter()
    driver.warm()
    driver.setup_split = {"system_s": t1 - t0, "traffic_s": t2 - t1,
                          "warm_s": time.perf_counter() - t2}
    return cell, system, driver, spans


def check(driver, outcome: Outcome, seed: int, limits: dict) -> dict:
    """Compare a seeded sample of the window's answers with the plain
    reference: the numbers compared, each with its limit."""
    from chipbench import reference
    rng = np.random.default_rng(np.random.SeedSequence(
        [37, int(seed) % (1 << 64)]))
    samples = driver.sample(rng)
    params = driver.system.reference_params()
    gaps = [reference.gap(ans, reference.report(tr, params, surface))
            for tr, ans, surface in samples]
    return {"max_gap": {"value": max(gaps, default=float("inf")),
                        "limit": limits["max_gap"]},
            "failed": {"value": outcome.failed, "limit": 0}}


def run_cell(args, bench: dict, device: dict, t0: float, *,
             config: dict | None = None, traffic: dict | None = None,
             limits: dict | None = None) -> dict:
    """Everything of a run after the look for a chip: set-up, the
    measured window, the metrics and the output check.  Returns the
    result line as a dict."""
    cell = cell_of(bench, args.workload)
    peaks = load_json(BENCH_DIR / "peaks.json")["devices"]
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"

    def say(msg: str) -> None:
        log(f"{tag} {msg}")

    if limits is None:
        limits = load_json(BENCH_DIR / "limits" / f"{cell['name']}.json")
    enable_caches()
    compiles = CompileLog()
    compiles.install()

    cell, system, driver, spans = prepare(args.workload, args.seed,
                                          bench=bench, config=config,
                                          traffic=traffic)
    setup_s = time.perf_counter() - t0
    split = " ".join(f"{k}={v:.3f}" for k, v in driver.setup_split.items())
    say(f"setup_s={setup_s:.3f} ({split}) compiles={compiles.count} "
        f"compile_s={compiles.seconds:.3f} cache_hits={compiles.cache_hits}")

    import jax
    before = compiles.count
    trace = None
    if args.trace:
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        spans.annotate = True
        # host spans and device activity only: the Python tracer and the
        # runtime's own host events would slow the host path they time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(PROFILE_DIR), profiler_options=opts)
    host = HostLog()
    host.start()
    with spans("window"):
        outcome = driver.run(args.seconds)
    host = host.stop()
    if args.trace:
        jax.profiler.stop_trace()
        spans.annotate = False
    say(f"compiles_in_window={compiles.count - before} "
        f"window_s={outcome.window_s:.3f} "
        f"counters={json.dumps(outcome.counters)}")
    _, w0, w1 = spans.records[-1]
    say(f"host_in_window={json.dumps(host)} spans_ms(count, median, "
        f"max)={json.dumps(span_summary(spans.records, w0, w1))}")
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device,
                  memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    if args.trace:
        from chipbench import trace_reduce
        path = glob.glob(str(PROFILE_DIR / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))
        trace = trace_reduce.reduce(trace_reduce.load(path[0]))
        shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        say(f"trace busy_s={trace['busy_s']} window_s={trace['window_s']} "
            f"busy_in_s={json.dumps(trace['busy_in_s'])}")

    run = Run(setup_s, outcome, spans.records, trace,
              peaks[device["kind"]])
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, kind, cell["name"]):
        value = read_metric(m["name"], run)
        if value is None:
            if kind == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing in cell {cell['name']}")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    driver.release()
    t_check = time.perf_counter()
    checks = check(driver, outcome, args.seed, limits)
    say(f"reference check took {time.perf_counter() - t_check:.3f} s")
    for name, c in checks.items():
        say(f"compared {name}={c['value']!r} limit={c['limit']!r}")
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": outcome.attempted, "failed": outcome.failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = checks
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = cell_of(bench, args.workload)
    peaks = load_json(BENCH_DIR / "peaks.json")["devices"]
    try:
        device = device_info(cell["chips"], peaks)
    except NoChip as e:
        log(f"chipbench: {e}; nothing was run")
        return 2
    result = run_cell(args, bench, device, t0)
    print(json.dumps(result), flush=True)
    return 0
