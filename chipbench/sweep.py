"""The open-loop knee, found once on the chip: a cell's open-loop
traffic at a list of rates, in one process, each for a short window.

    python3 chipbench/sweep.py --workload <name> --rates 100,200,400

For each rate it prints the latencies, how late the generator ran, the
deepest queue and whether the backlog grew over the window (the mean
latency of the last quarter of requests against the first quarter).
The knee is the highest rate whose backlog did not grow; the cell's
traffic file fixes four fifths of it.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def main(argv=None) -> int:
    from chipbench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 303)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.cell_of(bench, args.workload)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")["devices"]
    try:
        harness.device_info(cell["chips"], peaks)
    except harness.NoChip as e:
        harness.log(f"sweep: {e}; nothing was run")
        return 2
    harness.enable_caches()
    _, system, driver, _ = harness.prepare(args.workload, args.seed,
                                           bench=bench)
    for rate in (float(r) for r in args.rates.split(",")):
        driver.rate = rate
        driver.answers.clear()
        out = driver.run(args.seconds)
        lat = np.asarray(out.latencies_s) * 1e3
        q = max(len(lat) // 4, 1)
        print(json.dumps({
            "rate_per_s": rate, "requests": out.attempted,
            "failed": out.failed, "window_s": out.window_s,
            "completed_per_s": len(lat) / out.window_s,
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "first_quarter_ms": float(lat[:q].mean()),
            "last_quarter_ms": float(lat[-q:].mean()),
            "queue_max": out.counters["queue_max"],
            "late_ms_max": out.counters["generator_late_ms_max"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    import pathlib
    ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
