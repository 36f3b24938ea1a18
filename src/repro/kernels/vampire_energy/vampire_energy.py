"""Pallas TPU kernels: fused (traces x vendors) VAMPIRE energy.

The batched kernel family behind ``impl='pallas'`` (the unified estimator
protocol's fast path).  Two kernels split the work exactly where the model
does:

1. :func:`batched_features_pallas` — the **param-independent feature
   kernel**.  Consumes a padded TraceBatch's data stream once: per-line
   popcount and bus-XOR toggle popcount (the O(N x 512 bit) work, fusing
   the ``kernels/popcount`` and ``kernels/toggle`` bodies into one VMEM
   pass) with validity masking over NOP/dt=0 pad rows.  The stream is
   word-major ``(16, T, N)``, so each of the 16 words is an ``(8, block_n)``
   tile and the per-line sum is 16 elementwise adds.  Runs ONCE per batch;
   its outputs are shared by every vendor.

2. :func:`batched_energy_pallas` — the **per-vendor fused current/energy
   kernel**, gridded over ``(vendors, trace blocks, command blocks)`` by
   ``kernels.common.energy_grid_call``.  For each vendor it fuses the
   (interleave-mode, op) coefficient select of paper Eq. 2 (masked sum —
   no per-lane gathers on the VPU), the structural bank factor and
   open-bank background (8-way selects over the bank index and the packed
   open-bank bits), the I/O-driver term, the bank-state background
   integrator with burst crediting, ACT/REF charges with the per-(bank,
   row-band) structural surface factor (gathered into a per-command plane
   by the assembler — a VMEM multiply here, not a kernel gather), the
   optional ``ones_quad`` curvature (so the *true* simulator params ride
   the same kernel during characterization), and the pad-row weight mask.
   The vendor's scalars come from SMEM; each grid cell writes one
   lane-dense (8, 128) row of partial sums, reduced to the (traces,
   vendors) matrix outside.

   Passing ``cells`` (the int32 structural cell index of every command)
   switches the same launch to the ``mode='surface'`` reduction: the
   identical fused charge body, with each cell's partial sum written to
   its own lane -> the (traces, vendors, banks, row_bands) surface.

The index bookkeeping that decides bank state / interleave mode / previous
line (``energy_model.structural_state``) stays in vectorized jnp, and is
not cheap: gathering each command's previous line, bank and column took
~37 ns a command on a TPU v5e, most of the served estimate's device time.
At the ring's and the fleet's row lengths it reads them gather-free, as
exclusive cummaxes of packed (position, 16-bit half) keys and one-hot
selects over the 8 banks (``energy_model.gather_free``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dram import N_BANKS, TIMING
from repro.core.energy_model import N_SURFACE_CELLS
from repro.kernels.common import (TRACE_BLOCK, energy_grid_call,
                                  interpret_default, pad_to)
from repro.kernels.popcount.popcount import _popcount_u32

BLOCK_N = 512
LINE_BITS = 512.0
_T_BURST = float(TIMING.tBURST)

# layout of the packed per-vendor scalar row (see pack_params): the
# (mode, op, coeff) Eq. 2 table, the scalar leaves in ``_SCAL_FIELDS``
# order, then the per-bank open delta, read factor and write factor
_SCAL_FIELDS = ("i2n", "q_actpre", "row_ones_slope", "q_ref", "i_pd",
                "io_read_ma_per_one", "io_write_ma_per_zero", "ones_quad",
                "i_pd_slow", "i_actpd", "i_sr")
_COEFF0 = 0
_SCAL0 = _COEFF0 + 4 * 2 * 3
_OPEN0 = _SCAL0 + len(_SCAL_FIELDS)
_RD0 = _OPEN0 + N_BANKS
_WR0 = _RD0 + N_BANKS


def pack_params(stacked):
    """Pack a stacked (leading vendor axis) ``PowerParams`` into the one
    (V, 59) f32 scalar table the energy kernel reads from SMEM (layout:
    the ``_COEFF0``/``_SCAL0``/``_OPEN0``/``_RD0``/``_WR0`` offsets)."""
    n = stacked.i2n.shape[0]
    cols = [stacked.datadep.reshape(n, -1)]
    cols += [getattr(stacked, f).reshape(n, 1) for f in _SCAL_FIELDS]
    cols += [stacked.bank_open_delta, stacked.bank_read_factor,
             stacked.bank_write_factor]
    return jnp.concatenate([c.astype(jnp.float32) for c in cols], axis=1)


# ---------------------------------------------------------------------------
# 1. param-independent feature kernel
# ---------------------------------------------------------------------------
def _features_kernel(data_ref, prev_ref, tmask_ref, ones_ref, togg_ref):
    ones = togg = jnp.zeros(ones_ref.shape, jnp.int32)
    for w in range(data_ref.shape[0]):               # 16 words per line
        data = data_ref[w]                            # (8, B) uint32
        ones = ones + _popcount_u32(data)
        togg = togg + _popcount_u32(jnp.bitwise_xor(data, prev_ref[w]))
    ones_ref[...] = ones.astype(jnp.float32)
    togg_ref[...] = togg.astype(jnp.float32) * tmask_ref[...]


def batched_features_pallas(data, prev, tmask, block_n: int = BLOCK_N,
                            interpret: bool | None = None):
    """(T, N, 16) u32 data/prev lines + (T, N) f32 toggle-validity mask
    -> ((T, N) ones, (T, N) toggles) as f32, in one fused pass."""
    if interpret is None:
        interpret = interpret_default()
    n_traces, n_cmds = tmask.shape

    def tile(x, t_axis):
        x, _ = pad_to(x, TRACE_BLOCK, axis=t_axis)
        return pad_to(x, block_n, axis=t_axis + 1)[0]

    # word-major: every word of the line is its own (T, N) plane
    data = tile(jnp.moveaxis(data.astype(jnp.uint32), -1, 0), 1)
    prev = tile(jnp.moveaxis(prev.astype(jnp.uint32), -1, 0), 1)
    tmask = tile(tmask.astype(jnp.float32), 0)
    words, t_pad, n_pad = data.shape
    spec_w = pl.BlockSpec((words, TRACE_BLOCK, block_n),
                          lambda t, i: (0, t, i))
    spec_t = pl.BlockSpec((TRACE_BLOCK, block_n), lambda t, i: (t, i))
    plane = jax.ShapeDtypeStruct((t_pad, n_pad), jnp.float32)
    ones, togg = pl.pallas_call(
        _features_kernel,
        grid=(t_pad // TRACE_BLOCK, n_pad // block_n),
        in_specs=[spec_w, spec_w, spec_t],
        out_specs=[spec_t, spec_t],
        out_shape=[plane, plane],
        interpret=interpret,
        name="vampire_features",
    )(data, prev, tmask)
    return ones[:n_traces, :n_cmds], togg[:n_traces, :n_cmds]


# ---------------------------------------------------------------------------
# 2. per-vendor fused current/energy kernel
# ---------------------------------------------------------------------------
# (T, N) feature-plane order shared by the kernel body and the ops wrapper;
# ``bank`` (index) and ``open_bits`` (bit b set while bank b is open) are
# int32, the rest f32
FEATURE_PLANES = ("ones", "togg", "op", "mode", "dt", "is_rw", "is_act",
                  "is_ref", "pd", "row_ones", "w", "bank", "open_bits")


def _masked_charge(planes, vendor_planes, prm):
    """The fused per-command charge body of the scalar-sum and the
    surface-cell reductions.  ``planes`` are this block's
    :data:`FEATURE_PLANES` tiles, ``vendor_planes`` is ``(surf,)``, this
    vendor's per-command structural ACT factor (gathered by the
    assembler), and ``prm(k)`` reads the vendor's packed scalar ``k``.
    Returns the masked charge tile in mA*cycles."""
    (ones, togg, op, mode, dt, is_rw, is_act, is_ref, pd, row_ones, w,
     bank, open_bits) = planes
    (surf,) = vendor_planes
    i2n, q_actpre, slope, q_ref_chg, i_pd, io_r, io_w, ones_quad, \
        i_pd_slow, i_actpd, i_sr = (prm(_SCAL0 + k)
                                    for k in range(len(_SCAL_FIELDS)))

    # the per-bank structural terms: open-bank background delta summed
    # over the open bits, read/write factor selected by the bank index
    bg_delta = jnp.zeros_like(ones)
    rd_fac = jnp.zeros_like(ones)
    wr_fac = jnp.zeros_like(ones)
    for b in range(N_BANKS):
        is_open = ((open_bits >> b) & 1) == 1
        bg_delta = bg_delta + jnp.where(is_open, prm(_OPEN0 + b), 0.0)
        rd_fac = jnp.where(bank == b, prm(_RD0 + b), rd_fac)
        wr_fac = jnp.where(bank == b, prm(_WR0 + b), wr_fac)

    # background current from the bank state and the background-state code
    # carried in the ``pd`` plane (energy_model.BG_*: 0 active, 1 fast PDN,
    # 2 slow PDN, 3 active PDN, 4 self-refresh) — the kernel twin of
    # ``energy_model.background_current``
    i_low = jnp.where(pd == 1.0, i_pd,
                      jnp.where(pd == 2.0, i_pd_slow,
                                jnp.where(pd == 3.0, i_actpd, i_sr)))
    i_bg = jnp.where(pd == 0.0, i2n + bg_delta, i_low)

    # paper Eq. 2: masked (mode, op) coefficient select + quad curvature
    cur = jnp.zeros_like(ones)
    for m in range(4):
        for o in range(2):
            sel = ((mode == m) & (op == o)).astype(jnp.float32)
            k = _COEFF0 + (m * 2 + o) * 3
            c0, c1, c2 = prm(k), prm(k + 1), prm(k + 2)
            base = c0 + c1 * ones + c2 * togg
            base = base + ones_quad * c1 * ones * (ones / LINE_BITS - 0.5)
            cur = cur + sel * base
    io_cur = jnp.where(op == 0, io_r * ones, io_w * (LINE_BITS - ones))
    i_rw = cur * jnp.where(op == 0, rd_fac, wr_fac) + io_cur

    # the integrator: background over the slot, burst crediting, ACT/REF
    burst = jnp.minimum(dt, _T_BURST)
    charge = i_bg * dt
    charge = charge + is_rw * (i_rw - i_bg) * burst
    charge = charge + is_act * q_actpre * (1.0 + slope * row_ones) * surf
    charge = charge + is_ref * q_ref_chg
    return charge * w


def batched_energy_pallas(feats: dict, table, block_n: int = BLOCK_N,
                          interpret: bool | None = None, cells=None,
                          grid_layout: str = "vti") -> jax.Array:
    """The (vendors, trace blocks, command blocks)-gridded charge
    reduction.

    ``feats`` maps :data:`FEATURE_PLANES` names to (T, N) arrays, plus
    ``surf`` as the (V, T, N) per-command structural ACT factor; ``table``
    is :func:`pack_params`' (V, 59) scalar table.  Returns the (T, V)
    masked charge matrix in mA*cycles — or, when ``cells`` (the (T, N)
    structural cell index) is passed, the (T, V, CELLS) charge
    decomposition of ``mode='surface'``.  ``grid_layout`` picks the
    grid-major order (``kernels.common.grid_maps``) — pure scheduling, the
    partial sums are identical either way."""
    if interpret is None:
        interpret = interpret_default()
    planes = [feats[n] for n in FEATURE_PLANES]
    return energy_grid_call(_masked_charge, planes, table,
                            name=("vampire_energy" if cells is None
                                  else "vampire_surface"),
                            vendor_planes=[feats["surf"]], cells=cells,
                            n_cells=N_SURFACE_CELLS, block_n=block_n,
                            interpret=interpret, grid_layout=grid_layout)
