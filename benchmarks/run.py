"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Usage:
    PYTHONPATH=src python -m benchmarks.run [--only idd,validation]
"""
from __future__ import annotations

import argparse
import sys
import traceback

MODULES = [
    ("idd", "benchmarks.bench_idd"),                    # Figs 5-14
    ("datadep", "benchmarks.bench_datadep"),            # Figs 15-16, Tbl 2/5
    ("toggle", "benchmarks.bench_toggle"),              # Fig 18
    ("structural", "benchmarks.bench_structural"),      # Figs 19-22
    ("generational", "benchmarks.bench_generational"),  # Fig 23
    ("validation", "benchmarks.bench_validation"),      # Fig 24
    ("apps", "benchmarks.bench_apps"),                  # Fig 25
    ("encodings", "benchmarks.bench_encodings"),        # Fig 26
    ("applications", "benchmarks.bench_applications"),  # Sec 9.3 examples
    ("throughput", "benchmarks.bench_throughput"),      # ours
    ("estimate", "benchmarks.bench_estimate"),          # ours (PR 2)
    ("model_api", "benchmarks.bench_model_api"),        # ours (PR 3)
    ("kernels", "benchmarks.bench_kernels"),            # ours (PR 4)
    ("analysis", "benchmarks.bench_analysis"),          # ours (PR 7)
    ("serve", "benchmarks.bench_serve"),                # ours (PR 8)
    ("roofline", "benchmarks.bench_roofline"),          # deliverable (g)
    ("fleetscale", "benchmarks.bench_fleetscale"),      # ours (PR 9)
    ("recalibrate", "benchmarks.bench_recalibrate"),    # ours (PR 10)
]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated subset of benchmark names")
    args = p.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name, modpath in MODULES:
        if only and name not in only:
            continue
        try:
            mod = __import__(modpath, fromlist=["run"])
            for line in mod.run():
                print(line)
        except Exception:
            failures += 1
            print(f"{name},0,ERROR")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
