"""The readings the output check's limit is set from, on the chip, at a
cell's own size and load (the benchmark's own runs never run this):

    python3 chipbench/control.py --workload <name> --seeds 12 --seconds 3

One process builds the cell's system once; then, for each seed, a fresh
driver of the cell's traffic warms up, measures a short window, and on
the seeded sample of answers that a run checks, computes two gaps to the
float64 reference: the program's, and the control's (the plain
reference computed in bfloat16, float32 sums).  Each is the widest gap
over the sample, the number a run compares.  The lower reading is the
largest of the program's over the seeds, the upper reading the smallest
of the control's.  One JSON line per seed, then a summary.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    from chipbench import harness, reference
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.cell_of(bench, args.workload)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")["devices"]
    try:
        device = harness.device_info(cell["chips"], peaks)
    except harness.NoChip as e:
        harness.log(f"control: {e}; nothing was run")
        return 2
    harness.enable_caches()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    _, system, driver, _ = harness.prepare(args.workload, seeds[0],
                                           bench=bench)
    loop = sys.modules[type(driver).__module__]
    params = system.reference_params()
    program, control = [], []
    for k, seed in enumerate(seeds):
        if k:
            driver = loop.Driver(system, driver.traffic, seed,
                                 harness.Spans())
            driver.warm()
        outcome = driver.run(args.seconds)
        rng = np.random.default_rng(np.random.SeedSequence(
            [37, seed % (1 << 64)]))
        t0 = time.perf_counter()
        gp, gc = [], []
        for tr, ans, surface in driver.sample(rng):
            ref = reference.report(tr, params, surface)
            gp.append(reference.gap(ans, ref))
            gc.append(reference.gap(reference.report(
                tr, params, surface, dtype=reference.BFLOAT16), ref))
        program.append(max(gp))
        control.append(max(gc))
        print(json.dumps({"seed": seed, "answers": len(gp),
                          "failed": outcome.failed,
                          "program_gap": max(gp),
                          "control_gap": max(gc),
                          "control_gap_least_answer": min(gc),
                          "reference_s": time.perf_counter() - t0}),
              flush=True)
    print(json.dumps({"workload": args.workload, "device": device,
                      "seeds": len(seeds), "lower": max(program),
                      "upper": min(control),
                      "program_gaps": program, "control_gaps": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    import pathlib
    ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
