"""Kernel-grid autotuner for the ``(vendors, traces, blocks)`` families.

The fused kernels (``vampire_energy``, ``baseline_energy``) historically
launched with one hand-set command-axis block size (``BLOCK_N = 512``) and
one grid layout (vendor-major).  Neither was ever tuned: the best block
depends on the backend's VMEM/cache geometry and on how much of the padded
command axis a trace actually fills, and the best grid-major order depends
on which operand (the per-vendor parameter blocks vs the per-trace feature
planes) is cheaper to keep resident across consecutive grid cells.

This module is the small registry the dispatch paths consult:

* :func:`best_config` — the committed winner for the current
  ``(backend, family, shape-bucket)``, falling back to the historical
  defaults when the table has no entry.  Consulted by the
  ``resolve_impl``-dispatched assemblers (``kernels/*/ops.py``) whenever
  the caller does not pin ``block_n``/``grid_layout`` explicitly.
* :func:`sweep` — time a family's dispatch over the candidate
  (block, layout) grid for a set of shapes and return the winners.
  In interpret mode (any non-TPU/GPU backend without an override) every
  grid cell is a Python-loop iteration, so the candidate set is capped to
  the large blocks — the sweep is exempt from being a real tuning pass
  there and exists to keep CI time bounded while still recording choices.
* :func:`update_table` — merge sweep winners into the committed JSON
  table (``kernels/autotune_table.json``); ``python -m
  repro.kernels.autotune`` regenerates the current backend's entries.

The winners are cached per (backend, shape-bucket): shapes bucket to
powers of two, exactly like the serving ring's pad-shape vocabulary, so a
handful of table rows covers every production launch and ``block_n``
stays a static jit argument with a bounded number of distinct values.
"""
from __future__ import annotations

import functools
import json
import os
import pathlib
import time

import jax

from repro.kernels.common import interpret_default

TABLE_PATH = pathlib.Path(__file__).with_name("autotune_table.json")

#: command-axis block candidates (powers of two bracketing the historical
#: hand-set default)
CANDIDATE_BLOCKS = (128, 256, 512, 1024)
#: interpret-mode cap: each grid cell is a Python iteration, so small
#: blocks multiply wall-clock superlinearly — only the coarse blocks are
#: worth timing there
COARSE_BLOCKS = (512, 1024)
#: grid-major orders: vendor-major (parameters resident across traces) vs
#: trace-major (feature planes resident across vendors)
CANDIDATE_LAYOUTS = ("vti", "tvi")

#: the tuned dispatch families and their historical defaults
FAMILIES = ("vampire_energy", "baseline_energy")
DEFAULT_BLOCK = 512
DEFAULT_LAYOUT = "vti"


def backend_key() -> str:
    """The table's backend partition: the raw backend name for compiled
    launches, ``<backend>-interpret`` under the Pallas interpreter — the
    interpreter's cost model (Python loop over grid cells) is unrelated to
    the compiled one, so winners never cross-contaminate."""
    backend = jax.default_backend()
    return f"{backend}-interpret" if interpret_default() else backend


def shape_bucket(n_traces: int, n_cmds: int) -> str:
    """Power-of-two shape bucket, e.g. ``t32n4096`` — the same rounding
    the serving ring applies to pad shapes, so one table row covers every
    launch that lands in the bucket."""
    def up(v: int) -> int:
        return 1 << max(int(v) - 1, 0).bit_length()
    return f"t{up(n_traces)}n{up(n_cmds)}"


@functools.lru_cache(maxsize=1)
def _table() -> dict:
    try:
        with open(TABLE_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def reload_table() -> None:
    """Drop the cached table (tests / post-``update_table`` refresh)."""
    _table.cache_clear()


def best_config(family: str, n_traces: int, n_cmds: int) -> dict:
    """The tuned ``{"block_n": int, "layout": str}`` for this
    (backend, family, shape bucket), or the historical defaults when the
    committed table has no entry.  ``REPRO_AUTOTUNE=0`` disables the
    lookup entirely (pure defaults, e.g. for A/B timing the tuner)."""
    cfg = {"block_n": DEFAULT_BLOCK, "layout": DEFAULT_LAYOUT}
    if os.environ.get("REPRO_AUTOTUNE", "1") in ("0", "false", "False"):
        return cfg
    entry = (_table().get(backend_key(), {}).get(family, {})
             .get(shape_bucket(n_traces, n_cmds)))
    if entry:
        cfg["block_n"] = int(entry.get("block_n", DEFAULT_BLOCK))
        cfg["layout"] = str(entry.get("layout", DEFAULT_LAYOUT))
    return cfg


def choices(families=FAMILIES) -> dict:
    """The current backend's committed winners per family (for the bench
    artifacts to record alongside their timings)."""
    sub = _table().get(backend_key(), {})
    return {f: sub.get(f, {}) for f in families}


def candidate_space() -> tuple[tuple[int, ...], tuple[str, ...]]:
    """(blocks, layouts) to sweep on the current backend: the full grid on
    compiled backends, the interpret-exempt cap elsewhere (layout is
    meaningless to the interpreter's Python loop, so only the default is
    timed)."""
    if interpret_default():
        return COARSE_BLOCKS, (DEFAULT_LAYOUT,)
    return CANDIDATE_BLOCKS, CANDIDATE_LAYOUTS


def sweep(family: str, run_fn, shapes, blocks=None, layouts=None,
          repeats: int = 3) -> dict:
    """Time ``run_fn(n_traces, n_cmds, block_n, layout)`` over the
    candidate space for each ``(n_traces, n_cmds)`` shape.

    Returns ``{bucket: {"block_n", "layout", "us", "candidates_us"}}`` for
    the current backend.  ``run_fn`` must block on its result (the sweep
    calls ``jax.block_until_ready`` around it regardless) and is invoked
    once untimed per candidate to absorb compilation."""
    if blocks is None or layouts is None:
        auto_blocks, auto_layouts = candidate_space()
        blocks = auto_blocks if blocks is None else blocks
        layouts = auto_layouts if layouts is None else layouts
    out = {}
    for n_traces, n_cmds in shapes:
        timings = {}
        for layout in layouts:
            for block in blocks:
                jax.block_until_ready(
                    run_fn(n_traces, n_cmds, block, layout))   # compile
                best_s = float("inf")
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(
                        run_fn(n_traces, n_cmds, block, layout))
                    best_s = min(best_s, time.perf_counter() - t0)
                timings[f"{layout}/b{block}"] = best_s * 1e6
        win = min(timings, key=timings.get)
        layout, block = win.split("/b")
        out[shape_bucket(n_traces, n_cmds)] = {
            "block_n": int(block), "layout": layout,
            "us": timings[win],
            "candidates_us": {k: round(v, 1) for k, v in timings.items()},
        }
    return out


def update_table(family: str, entries: dict, path=TABLE_PATH) -> dict:
    """Merge sweep winners for the current backend into the committed
    table and rewrite it (winners only — the per-candidate timings stay in
    the bench artifacts).  Returns the merged table."""
    try:
        with open(path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        table = {}
    rows = table.setdefault(backend_key(), {}).setdefault(family, {})
    for bucket, entry in entries.items():
        rows[bucket] = {"block_n": int(entry["block_n"]),
                        "layout": str(entry["layout"])}
    with open(path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
        f.write("\n")
    reload_table()
    return table


# ---------------------------------------------------------------------------
# Maintenance CLI: regenerate the current backend's table entries against
# the real dispatch paths (synthetic traces, vendor-true parameters).
# ---------------------------------------------------------------------------
def _family_runners():
    """family -> ``run_fn(n_traces, n_cmds, block_n, layout)`` over the
    production assemblers, memoizing the probe inputs per shape."""
    import jax.numpy as jnp

    from repro.core import device_sim, idd_loops
    from repro.core import params as P
    from repro.core.baselines_power import BASELINE_IDD_KEYS
    from repro.core.estimate_batch import TraceBatch
    from repro.core.fleet import stack_params
    from repro.kernels.baseline_energy import ops as bops
    from repro.kernels.vampire_energy import ops as vops

    stacked = stack_params([device_sim.true_vendor_params(v)
                            for v in range(3)])
    table = jnp.asarray(
        [[float(P.MEASURED_IDD.get(k, (100.0, 100.0, 100.0))[v])
          for k in BASELINE_IDD_KEYS] for v in range(3)], jnp.float32)

    @functools.lru_cache(maxsize=8)
    def batch(n_traces: int, n_cmds: int) -> TraceBatch:
        reps = n_cmds // 10 + 1          # validation_sweep(8): 10 cmds/rep
        trs = [idd_loops.validation_sweep(8, reps=reps)
               for _ in range(n_traces)]
        tb = TraceBatch.from_traces(trs)
        trace = jax.tree_util.tree_map(lambda x: x[:, :n_cmds], tb.trace)
        return TraceBatch(trace, tb.weight[:, :n_cmds].astype(jnp.float32))

    def vampire_run(n_traces, n_cmds, block_n, layout):
        tb = batch(n_traces, n_cmds)
        return vops.batched_charge_matrix(tb.trace, tb.weight, stacked,
                                          block_n=block_n,
                                          grid_layout=layout)

    def baseline_run(n_traces, n_cmds, block_n, layout):
        tb = batch(n_traces, n_cmds)
        return bops.baseline_charge_matrix(tb.trace, tb.weight, table,
                                           "micron", block_n=block_n,
                                           grid_layout=layout)

    return {"vampire_energy": vampire_run, "baseline_energy": baseline_run}


def main(argv=None) -> int:  # pragma: no cover - maintenance entry point
    import argparse
    ap = argparse.ArgumentParser(prog="python -m repro.kernels.autotune",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", default="8x1024,32x1024,128x4096",
                    help="comma-separated TRACESxCOMMANDS probe shapes")
    ap.add_argument("--dry-run", action="store_true",
                    help="print winners without rewriting the table")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    shapes = [tuple(int(v) for v in s.split("x"))
              for s in args.shapes.split(",")]
    for family, run_fn in _family_runners().items():
        winners = sweep(family, run_fn, shapes)
        for bucket, entry in winners.items():
            print(f"{backend_key()}/{family}/{bucket}: "
                  f"block_n={entry['block_n']} layout={entry['layout']} "
                  f"({entry['us']:.0f}us)")
        if not args.dry_run:
            update_table(family, winners)
    return 0


if __name__ == "__main__":  # pragma: no cover
    import sys
    sys.exit(main())
