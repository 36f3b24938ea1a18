"""Run one cell of the chip benchmark, from the root of a checkout:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cells, their metrics and bounds are in ``BENCHMARK.json``; what one
run does is in ``harness.py``.  Without a TPU (or with fewer chips than
the cell asks for, or a device kind missing from ``peaks.json``) it
prints no result and exits 2.
"""
import time

T0 = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

if __name__ == "__main__":
    from chipbench import harness
    sys.exit(harness.main(sys.argv[1:], T0))
