"""Bring-up smoke run of the VAMPIRE estimation stack on a TPU.

Drives the served estimation path once, through the entry points a user
calls, at the sizes its users run, and checks every answer against the
repo's own references (``impl='vectorized'`` and the per-command
``impl='reference'`` oracle, at the ``rtol=1e-4`` the serving benchmark
uses).  Run from the root of a checkout:

    python chip_smoke.py             # phases (a)-(f) on one chip
    python chip_smoke.py --chips 4   # the mesh paths on four chips

One chip runs, in one process:

  (a) device check: a TPU, and ``impl='pallas'`` compiled (not interpreted);
  (b) campaign fit of the paper's 50-module, three-vendor fleet with the
      probes measured through ``impl='pallas'``;
  (c) an ``EstimationService(impl='pallas')`` answering windows of SPEC
      CPU2006-mix traces across the ring's length buckets (up to 64 traces
      of up to 16384 commands, three vendors), in modes ``mean`` and
      ``surface``;
  (d) ``fleet_surface_energy`` over the 50-module fleet, pallas against
      vectorized;
  (e) streaming recalibration ticks ending in a hot-swap that compiles
      nothing new;
  (f) the LM power report of a full-width ``qwen2.5-3b`` decode (random
      weights from ``--seed``).

``--chips 4`` runs only the multi-device paths on a ``(data=1, model=4)``
mesh: the service's trace-axis ``shard_map`` and the fleet surface's
module-axis ``shard_map`` (50 modules pad to 52), each compared bitwise
with the same call on one device.

Each phase prints one line with its wall time and the XLA compiles it
triggered (count, seconds, persistent-cache hits).  Any failed check
raises, so the exit code is non-zero.  The last line of standard output
is one JSON object naming the device.  Without a TPU the script prints no
result and exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

RTOL = 1e-4
#: (traces, longest trace) per service window: every ring length bucket,
#: up to the ring's largest window
WINDOWS = ((8, 256), (16, 1024), (32, 4096), (64, 16384))
LM_ARCH = "qwen2.5-3b"


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def check_close(actual, desired, what: str, rtol: float = RTOL) -> None:
    try:
        np.testing.assert_allclose(np.asarray(actual), np.asarray(desired),
                                   rtol=rtol)
    except AssertionError as e:
        raise SmokeFailure(f"{what}: {e}") from None


def check_equal(actual, desired, what: str) -> None:
    try:
        np.testing.assert_array_equal(np.asarray(actual), np.asarray(desired))
    except AssertionError as e:
        raise SmokeFailure(f"{what}: {e}") from None


def row_of(report, i: int):
    """Row ``i`` (one trace) of a batched EnergyReport."""
    return type(report)(*(leaf[i] for leaf in report))


def check_reports(actual, desired, what: str, exact: bool = False) -> None:
    """Leaf-for-leaf comparison of two EnergyReports."""
    for name, a, d in zip(desired._fields, actual, desired):
        if exact:
            check_equal(a, d, f"{what} leaf {name}")
        else:
            check_close(a, d, f"{what} leaf {name}")


class CompileLog:
    """XLA backend compiles (persistent-cache loads included, which are
    cheap) and persistent-cache hits since it was made, read from the
    program's span recorder (``repro.runtime.spans``)."""

    def __init__(self):
        from repro.runtime.spans import RECORDER
        self._recorder = RECORDER
        self._t0 = time.perf_counter()
        self._hits0 = RECORDER.cache_hits

    def snapshot(self) -> tuple[int, float, int]:
        from repro.runtime.spans import COMPILE
        recs = self._recorder.inside(self._t0, time.perf_counter())
        if recs is None:
            raise SmokeFailure("the span recorder dropped compile records")
        secs = [r.t1 - r.t0 for r in recs if r.name == COMPILE]
        return len(secs), sum(secs), self._recorder.cache_hits - self._hits0

    @property
    def count(self) -> int:
        return self.snapshot()[0]

    @property
    def cache_hits(self) -> int:
        return self.snapshot()[2]


@contextlib.contextmanager
def phase(name: str, log: CompileLog, totals: dict):
    """Time one phase and print its line (only if it succeeded)."""
    extra: dict = {}
    c0, s0, h0 = log.snapshot()
    t0 = time.perf_counter()
    yield extra
    wall = time.perf_counter() - t0
    c1, s1, h1 = log.snapshot()
    totals["wall_s"] += wall
    totals["compile_s"] += s1 - s0
    detail = " ".join(f"{k}={v}" for k, v in extra.items())
    print(f"[phase {name}] ok wall_s={wall:.1f} compiles={c1 - c0} "
          f"compile_s={s1 - s0:.1f} cache_hits={h1 - h0} {detail}".rstrip(),
          flush=True)


# ---------------------------------------------------------------------------
# workload: SPEC CPU2006-mix traces (paper Fig 25 apps), made from the seed
# ---------------------------------------------------------------------------
def spec_windows(windows, seed: int):
    """One list of SPEC-mix traces per ``(count, longest)`` window, trace
    lengths spread over ``(longest/2, longest]`` commands so every window
    lands in its own ring length bucket."""
    from repro.core import traces
    ratio: dict[str, float] = {}

    def trace_of(app, target: int):
        app = dataclasses.replace(app, seed=app.seed + 1000 * seed)
        if app.name not in ratio:          # commands per request, per app
            probe = traces.app_trace(app, n_requests=512)
            ratio[app.name] = probe.n / 512
        n_req = max(int(target / ratio[app.name]), 8)
        tr = traces.app_trace(app, n_requests=n_req)
        while tr.n > target:
            n_req = int(n_req * 0.95)
            tr = traces.app_trace(app, n_requests=n_req)
        return tr

    out, k = [], 0
    for count, longest in windows:
        trs = []
        for j in range(count):
            app = traces.SPEC_APPS[k % len(traces.SPEC_APPS)]
            k += 1
            trs.append(trace_of(app, int(longest * (0.55 + 0.45 * (j + 1)
                                                    / count))))
        out.append(trs)
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def device_check(chips: int) -> dict:
    import jax

    from repro.core import model_api
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform!r} devices")
    check(len(devices) >= chips,
          f"{chips} chips asked for, JAX found {len(devices)}")
    mode = model_api.impl_execution_mode("pallas")
    check(mode == "compiled", f"impl='pallas' runs in {mode} mode on TPU")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def campaign_fit(fleet, fit_kw):
    """Phase (b): the campaign with pallas probes, plus a probe-level
    check of the pallas measurement against the vectorized one."""
    from repro.core import characterize, model_api
    from repro.core import fleet as fleet_mod
    model = model_api.fit("vampire", fleet, fitter="campaign",
                          impl="pallas", **fit_kw)
    for v in model.vendors:
        for leaf in model.params(v):
            check(bool(np.all(np.isfinite(np.asarray(leaf)))),
                  f"vendor {v} fitted a non-finite parameter")
    plan = characterize.campaign_plan(probe_reps=fit_kw["probe_reps"],
                                      n_rows=fit_kw["n_rows"])
    points = list(plan.idd_points) + list(plan.probe_points)
    pal = fleet_mod.run_probes(fleet, points, impl="pallas", noisy=False)
    vec = fleet_mod.run_probes(fleet, points, impl="vectorized", noisy=False)
    check_close(pal, vec, "campaign probes pallas vs vectorized")
    return model, {"modules": len(fleet), "probes": len(points)}


def serve_windows(model, windows, mode: str, n_reference: int = 2):
    """Phase (c), one mode: every window through the pallas service,
    each trace checked against vectorized, a few against reference."""
    from repro.serving import EstimationService, ServiceConfig
    svc = EstimationService(model, ServiceConfig(impl="pallas", mode=mode))
    n_traces = n_cmds = 0
    for trs in windows:
        tickets, rejections = svc.submit_many(trs)
        check(not rejections, f"service rejected {len(rejections)} traces")
        check(svc.drain() == len(trs), "window not dispatched whole")
        rows = [svc.result(t) for t in tickets]
        vec = model.estimate(trs, mode=mode)
        for i, row in enumerate(rows):
            check_reports(row, row_of(vec, i),
                          f"mode={mode} trace {i} pallas vs vectorized")
        ref_idx = list(range(min(n_reference, len(trs))))
        ref = model.estimate([trs[i] for i in ref_idx], mode=mode,
                             impl="reference")
        for j, i in enumerate(ref_idx):
            check_reports(rows[i], row_of(ref, j),
                          f"mode={mode} trace {i} pallas vs reference")
        n_traces += len(trs)
        n_cmds += sum(int(tr.n) for tr in trs)
    m = svc.metrics()
    check(m.dispatches == len(windows), "one dispatch per window expected")
    return {"traces": n_traces, "commands": n_cmds,
            "programs": m.engine_programs}


def fleet_surface(fleet, trs, length: int):
    """Phase (d): the fleet-wide structural surface, pallas vs vectorized."""
    from repro.core.estimate_batch import bucketed_trace_batch
    from repro.core.fleet import fleet_surface_energy
    tb = bucketed_trace_batch(trs, len(trs), length)
    pal = fleet_surface_energy(fleet, tb.trace, tb.weight, impl="pallas")
    vec = fleet_surface_energy(fleet, tb.trace, tb.weight, impl="vectorized")
    check(pal.energy_pj.shape == (len(trs), len(fleet), 8, 8),
          f"surface shape {pal.energy_pj.shape}")
    check_reports(pal, vec, "fleet surface pallas vs vectorized")
    return {"traces": len(trs), "modules": len(fleet)}


def recalibration(model, fleet, trs, fit_kw, log: CompileLog):
    """Phase (e): telemetry ticks into a serving fitter, a planted drift
    step that triggers a refit, and a hot-swap that compiles nothing."""
    from repro.core import device_sim, model_api, recalibrate
    from repro.serving import EstimationService, ServiceConfig
    cfg = recalibrate.RecalConfig(probe_reps=fit_kw["probe_reps"],
                                  n_rows=fit_kw["n_rows"],
                                  probe_modules=fit_kw["probe_modules"],
                                  slice_size=100_000)
    ticks = 3
    drift = device_sim.DriftProcess(step_tick=ticks, step_frac=0.2)
    fitter = model_api.fit("vampire", fleet, fitter="streaming",
                           init_model=model, config=cfg)
    svc = EstimationService(model, ServiceConfig(impl="pallas"),
                            fitter=fitter)
    src = recalibrate.TelemetrySource(fleet, cfg, drift=drift)

    def score():
        tickets, rejections = svc.submit_many(trs)
        check(not rejections, "recalibration window rejected")
        svc.drain()
        return np.asarray([svc.result(t).energy_pj for t in tickets])

    before = score()
    programs = svc.engine.cache_size()
    for tick in range(1, ticks + 1):
        cur, idx = src.measure(tick)
        report = svc.observe_telemetry(cur, idx, tick)
    check(report.triggered, "the planted drift step did not trigger")
    check(svc.metrics().recalibrations >= 1, "no hot-swap happened")
    c0 = log.count
    after = score()
    check(log.count == c0, f"the hot-swapped dispatch compiled "
                           f"{log.count - c0} programs")
    check(svc.engine.cache_size() == programs,
          "the hot-swap added compiled programs")
    check(not np.array_equal(before, after),
          "the hot-swapped parameters did not change the answers")
    return {"ticks": ticks, "recalibrations": svc.metrics().recalibrations,
            "drift_score": f"{report.score:.2f}"}


def lm_power_report(seed: int):
    """Phase (f): the served LM decode with the power report at the
    architecture's published widths."""
    from repro.launch import serve
    job = serve.ServeJob(arch=LM_ARCH, smoke=False, power_report=True,
                         power_impl="pallas", decode_tokens=8, seed=seed)
    res = serve.run(job)
    check(res["tokens"].shape == (job.batch, job.decode_tokens),
          f"decoded tokens shape {res['tokens'].shape}")
    pw = res["power"]
    energy = np.asarray(pw["ddr_energy_pj_per_seq_step"])
    check(energy.shape == (job.batch, len(pw["vendors"])),
          f"power report shape {energy.shape}")
    check(bool(np.all(np.isfinite(energy)) and np.all(energy > 0)),
          "non-finite or non-positive DDR energy")
    check(bool(np.isfinite(pw["hbm_step_energy_uj"])
               and pw["hbm_step_energy_uj"] > 0), "bad HBM step energy")
    return {"arch": LM_ARCH, "decode_p50_ms": f"{res['decode_p50_ms']:.2f}",
            "uj_per_token": f"{pw['ddr_energy_uj_per_token_mean']:.3f}"}


def mesh_paths(trs, fleet, length: int):
    """The four-chip phase: both shard_map paths on a (data=1, model=4)
    mesh, each bitwise against the same call on one device."""
    import jax

    from repro.core import fleet as fleet_mod
    from repro.core.estimate_batch import bucketed_trace_batch
    from repro.core.vampire import reference_vampire
    from repro.launch.mesh import make_local_mesh
    from repro.serving import EstimationService, ServiceConfig
    mesh = make_local_mesh(data=1, model=4)
    model = reference_vampire()
    out = {}
    for mode in ("mean", "surface"):
        cfg = ServiceConfig(impl="pallas", mode=mode)
        sharded = EstimationService(model, cfg, mesh=mesh)
        plain = EstimationService(model, cfg)
        check(sharded.engine.n_shards == 4, "service mesh is not 4-wide")
        ts, _ = sharded.submit_many(trs)
        tp, _ = plain.submit_many(trs)
        sharded.drain(), plain.drain()
        for a, b in zip(ts, tp):
            check_reports(sharded.result(a), plain.result(b),
                          f"service mode={mode} sharded vs one device",
                          exact=True)
        tb = bucketed_trace_batch(trs, len(trs), length)
        rep = sharded.engine.dispatch(tb)
        n_dev = len(rep.energy_pj.sharding.device_set)
        check(n_dev == 4, f"service output spans {n_dev} devices")
        out[f"service_{mode}"] = "bitwise"

    tb = bucketed_trace_batch(trs, len(trs), length)
    sharded = fleet_mod.fleet_surface_energy(fleet, tb.trace, tb.weight,
                                             impl="pallas", mesh=mesh)
    plain = fleet_mod.fleet_surface_energy(fleet, tb.trace, tb.weight,
                                           impl="pallas")
    check_reports(sharded, plain, "fleet surface sharded vs one device",
                  exact=True)
    stacked = fleet_mod.pad_leading(fleet_mod.fleet_stacked(fleet, mesh),
                                    (-len(fleet)) % 4)
    charge = fleet_mod._sharded_surface_fn(mesh, True)(tb.trace, tb.weight,
                                                       stacked)
    n_dev = len(charge.sharding.device_set)
    check(n_dev == 4, f"fleet surface charge spans {n_dev} devices")
    out["fleet_surface"] = f"bitwise,{len(fleet)}->{stacked.i2n.shape[0]}"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.common import FIT_KW
    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found "
              f"{jax.devices()[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    log = CompileLog()
    totals = {"wall_s": 0.0, "compile_s": 0.0}
    print(f"compile cache: {cache_dir}", flush=True)

    with phase("a device", log, totals) as extra:
        device = device_check(args.chips)
        extra.update(kind=repr(device["kind"]), count=device["count"],
                     pallas="compiled")
    from repro.core import device_sim
    fleet = device_sim.make_fleet()

    if args.chips == 4:
        length = WINDOWS[2][1]
        trs = spec_windows(((8, length),), args.seed)[0]
        with phase("mesh4", log, totals) as extra:
            extra.update(mesh_paths(trs, fleet, length))
    else:
        windows = spec_windows(WINDOWS, args.seed)
        with phase("b campaign_fit", log, totals) as extra:
            model, info = campaign_fit(fleet, FIT_KW)
            extra.update(info)
        for mode in ("mean", "surface"):
            with phase(f"c service_{mode}", log, totals) as extra:
                extra.update(serve_windows(model, windows, mode))
        with phase("d fleet_surface", log, totals) as extra:
            extra.update(fleet_surface(fleet, windows[2][:8], WINDOWS[2][1]))
        with phase("e recalibration", log, totals) as extra:
            extra.update(recalibration(model, fleet, windows[1], FIT_KW,
                                       log))
        with phase("f lm_power_report", log, totals) as extra:
            extra.update(lm_power_report(args.seed))
    print(f"total wall_s={totals['wall_s']:.1f} "
          f"compile_s={totals['compile_s']:.1f} compiles={log.count} "
          f"cache_hits={log.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
