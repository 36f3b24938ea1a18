"""Shared Pallas kernel plumbing.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated everywhere else with ``interpret=True``, which executes the kernel
body in Python.  :func:`interpret_default` resolves the mode *per call* from
``jax.default_backend()`` — compiled on TPU, interpreted on CPU/GPU — so the
kernels are runnable on any backend without a hand-set flag, and a backend
selected after import (tests, ``jax.config`` changes) is still honoured.
Off-TPU, ``REPRO_PALLAS_INTERPRET`` may force either way (the CI
pallas-interpret job exports ``REPRO_PALLAS_INTERPRET=1``); on a TPU the
kernels always compile.

:func:`energy_grid_call` is the one ``pallas_call`` both fused energy
families (``vampire_energy``, ``baseline_energy``) launch through: a
``(vendors, trace blocks, command blocks)`` grid whose every block obeys the
TPU's (8, 128) tiling rule — per-command planes tile as ``(8, block_n)``,
per-vendor scalars ride in SMEM, and each (vendor, trace block) owns one
lane-dense ``(8, 128)`` output block that accumulates over the command
blocks.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: trace-axis block of the fused energy kernels: one (8, 128) f32 tile
TRACE_BLOCK = 8
LANES = 128


def interpret_default() -> bool:
    """Whether a kernel launched *now* should run in interpret mode:
    always compiled on TPU; elsewhere the ``REPRO_PALLAS_INTERPRET`` env
    override if set, else interpreted."""
    if jax.default_backend() == "tpu":
        return False
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return True


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x, multiple: int, axis: int = 0, value=0):
    """Pad axis up to a multiple (kernels require whole blocks)."""
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


def pad_batch(trace, weight, block_n: int):
    """Pad a (T, N) command batch to whole kernel tiles — traces to a
    multiple of :data:`TRACE_BLOCK`, commands to one of ``block_n`` — with
    all-zero slots (NOP, ``dt == 0``; ``dram.NOP == 0``) of zero weight,
    which change no result (the TraceBatch pad contract).

    The assemblers' wrappers call this BEFORE entering their jitted
    program, so a concrete batch pads in programs of its own and the
    kernels' program only ever sees whole tiles.  On a v5e, the
    characterization campaign's (348, 518) probe batch never finished
    when its pads shared the program with the kernels; aligned batches
    ran, and so did the same kernels fed padded arrays from outside."""
    t, n = weight.shape
    pt, pn = (-t) % TRACE_BLOCK, (-n) % block_n
    if not (pt or pn):
        return trace, weight

    def pad(x):
        return jnp.pad(x, [(0, pt), (0, pn)] + [(0, 0)] * (x.ndim - 2))
    return jax.tree_util.tree_map(pad, trace), pad(weight)


def pad_carry(carry, rows: int):
    """Pad a batch's segment carries (leading row axis) with zero rows up
    to ``rows``, the trace axis :func:`pad_batch` made; the pad rows have
    zero weight, so what they carry changes no result."""
    if carry is None:
        return None
    return jax.tree_util.tree_map(
        lambda x: jnp.pad(jnp.asarray(x), [(0, rows - x.shape[0])]
                          + [(0, 0)] * (x.ndim - 1)), carry)


def grid_maps(grid_layout: str, n_vendors: int, n_traces: int, grid_n: int):
    """The grid tuple plus an index-map builder for one grid-major order.

    ``'vti'`` iterates vendors outermost, keeping one trace block's
    planes resident across the vendor sweep of a command block; ``'tvi'``
    iterates trace blocks outermost, keeping one vendor's parameters
    resident instead.  The autotuner (``kernels/autotune``) picks per
    (backend, shape-bucket).  ``as_map`` lifts a ``(v, t, i) -> block
    index`` function into the grid's own coordinate order, so the kernels
    and BlockSpecs stay layout-agnostic."""
    if grid_layout == "tvi":
        grid = (n_traces, n_vendors, grid_n)

        def as_map(sel):
            return lambda t, v, i: sel(v, t, i)
    elif grid_layout == "vti":
        grid = (n_vendors, n_traces, grid_n)

        def as_map(sel):
            return lambda v, t, i: sel(v, t, i)
    else:
        raise ValueError(f"unknown grid_layout {grid_layout!r}")
    return grid, as_map


def _lane_partials(cw):
    """(8, B) -> (8, 128): fold the command axis onto the lanes with
    aligned static slices (B is a multiple of 128)."""
    acc = cw[:, :LANES]
    for k in range(LANES, cw.shape[1], LANES):
        acc = acc + cw[:, k:k + LANES]
    return acc


def _cell_partials(cw, cells, n_cells: int):
    """(8, B) charge + (8, B) int32 cell index -> (8, 128) with cell
    ``c``'s partial sum in lane ``c`` (``n_cells <= 128``)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (cw.shape[0], LANES), 1)
    acc = jnp.zeros((cw.shape[0], LANES), jnp.float32)
    for c in range(n_cells):
        part = jnp.sum(jnp.where(cells == c, cw, 0.0), axis=1, keepdims=True)
        acc = jnp.where(lane == c, part, acc)
    return acc


def energy_grid_call(charge_fn, planes, table, *, name: str,
                     vendor_planes=(), cells=None, n_cells: int = 0,
                     block_n: int, interpret: bool, grid_layout: str = "vti"):
    """Launch a fused per-command charge body over the ``(vendors, trace
    blocks, command blocks)`` grid and reduce it.

    ``planes`` are (T, N) per-command arrays, ``vendor_planes`` (V, T, N)
    per-(vendor, command) arrays, ``table`` the (V, P) f32 per-vendor
    scalars (read from SMEM).  ``charge_fn(planes, vendor_planes, prm)``
    gets each plane's (8, block_n) tile and ``prm(k)``, the current
    vendor's k-th scalar, and returns the masked (8, block_n) charge.
    The trace axis pads to a multiple of 8 and the command axis to
    ``block_n`` with zeros — pad slots must carry zero weight.

    ``name`` names the launch in the compiled program and the device
    trace.  Returns the (T, V) charge matrix, or with ``cells`` (the
    (T, N) int32 cell index of every command) the (T, V, n_cells)
    decomposition.  The whole reduction runs in the kernel, so the result
    of a trace or vendor does not depend on how many others share the
    launch.
    ``grid_layout`` is pure scheduling: every grid cell computes the same
    partial sums either way."""
    if block_n % LANES:
        raise ValueError(f"block_n={block_n} is not a multiple of {LANES}")
    n_traces = planes[0].shape[0]
    n_vendors, n_params = table.shape

    def tile(x, t_axis):
        x, _ = pad_to(x, TRACE_BLOCK, axis=t_axis)
        return pad_to(x, block_n, axis=t_axis + 1)[0]

    args = [tile(p, 0) for p in planes] + [tile(p, 1) for p in vendor_planes]
    if cells is not None:
        args.append(tile(cells.astype(jnp.int32), 0))
    t_pad, n_pad = args[0].shape
    grid_n = n_pad // block_n
    grid, as_map = grid_maps(grid_layout, n_vendors, t_pad // TRACE_BLOCK,
                             grid_n)
    spec_t = pl.BlockSpec((TRACE_BLOCK, block_n),
                          as_map(lambda v, t, i: (t, i)))
    spec_v = pl.BlockSpec((1, TRACE_BLOCK, block_n),
                          as_map(lambda v, t, i: (v, t, i)))
    # a unit axis keeps the SMEM block's last two dims whole: (1, P)
    spec_p = pl.BlockSpec((1, 1, n_params),
                          as_map(lambda v, t, i: (v, 0, 0)),
                          memory_space=pltpu.SMEM)
    n_planes, n_vplanes = len(planes), len(vendor_planes)

    def kernel(*refs):
        p_refs = refs[:n_planes]
        v_refs = refs[n_planes:n_planes + n_vplanes]
        table_ref, o_ref = refs[-2], refs[-1]
        cw = charge_fn([r[...] for r in p_refs], [r[0] for r in v_refs],
                       lambda k: table_ref[0, 0, k])
        if cells is None:
            part = _lane_partials(cw)
        else:
            part = _cell_partials(cw, refs[n_planes + n_vplanes][...],
                                  n_cells)
        # the command-block axis is innermost in every layout: the output
        # block stays resident across it and accumulates
        block = pl.program_id(2)

        @pl.when(block == 0)
        def _():
            o_ref[0] = part

        @pl.when(block > 0)
        def _():
            o_ref[0] = o_ref[0] + part

        if cells is None:
            @pl.when(block == grid_n - 1)
            def _():
                o_ref[0] = jnp.broadcast_to(
                    jnp.sum(o_ref[0], axis=1, keepdims=True), part.shape)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=([spec_t] * n_planes + [spec_v] * n_vplanes
                  + ([spec_t] if cells is not None else []) + [spec_p]),
        out_specs=pl.BlockSpec((1, TRACE_BLOCK, LANES),
                               as_map(lambda v, t, i: (v, t, 0))),
        out_shape=jax.ShapeDtypeStruct((n_vendors, t_pad, LANES),
                                       jnp.float32),
        interpret=interpret,
        name=name,
    )(*args, table.astype(jnp.float32)[:, None, :])
    # every sum happened in the kernel, in an order fixed by the tile
    # shapes alone, so sharding the trace or vendor axis cannot change a
    # bit of the result
    if cells is None:
        return out[:, :n_traces, 0].T                          # (T, V)
    return out[:, :n_traces, :n_cells].transpose(1, 0, 2)    # (T, V, C)
