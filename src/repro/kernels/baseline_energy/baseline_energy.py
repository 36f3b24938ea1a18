"""Pallas TPU kernel: fused (traces x vendors) datasheet-baseline energy.

The ``impl='pallas'`` path for the Micron-calculator and DRAMPower
estimators (``repro.core.baselines_power``).  Both physics are pure
per-command formulas over the shared structural facts (open-bank count,
power-down state) and a per-vendor datasheet IDD row, so one kernel body
per baseline, gridded over ``(vendors, traces, command blocks)`` exactly
like the VAMPIRE energy kernel (``kernels.common.energy_grid_call``),
covers the whole report matrix: per grid cell it reads one (8, BLOCK) tile
of every per-command plane plus this vendor's IDD row from SMEM and writes
one lane-dense row of masked partial charge sums.

IDD row layout follows ``baselines_power.BASELINE_IDD_KEYS``:
``(IDD0, IDD2N, IDD2P1, IDD3N, IDD4R, IDD4W, IDD5B, IDD2P0, IDD3P,
IDD6)`` — the low-power keys appended at the end.  The ``pd`` plane
carries the background-state code (``energy_model.BG_*``: 0 active,
1 fast PDN, 2 slow PDN, 3 active PDN, 4 self-refresh) as f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.baselines_power import BASELINE_IDD_KEYS, act_pair_charge
from repro.core.dram import TIMING
from repro.core.energy_model import N_SURFACE_CELLS
from repro.kernels.common import energy_grid_call, interpret_default

BLOCK_N = 512
_T = TIMING

# per-command (T, N) planes, in kernel argument order
PLANES = ("dt", "is_rd", "is_wr", "is_act", "is_ref", "open_banks", "pd", "w")
_N_IDD = len(BASELINE_IDD_KEYS)


def _masked_charge(kind: str, dt, is_rd, is_wr, is_act, is_ref, open_banks,
                   pd, w, any_act, idd):
    """The fused per-command baseline charge body shared by the scalar-sum
    and the surface-cell reductions.  ``any_act`` is the trace's any-ACT
    flag broadcast over its commands.  Returns the masked charge tile in
    mA*cycles."""
    idd0, idd2n, idd2p1, idd3n = idd[0], idd[1], idd[2], idd[3]
    idd4r, idd4w, idd5b = idd[4], idd[5], idd[6]
    idd2p0, idd3p, idd6 = idd[7], idd[8], idd[9]

    # state-code LUT over the ``pd`` plane — the kernel twin of
    # ``baselines_power._bg_lut``
    i_low = jnp.where(pd == 1.0, idd2p1,
                      jnp.where(pd == 2.0, idd2p0,
                                jnp.where(pd == 3.0, idd3p, idd6)))
    active = (pd == 0.0).astype(jnp.float32)

    burst = jnp.minimum(dt, float(_T.tBURST))
    q_act = act_pair_charge(idd0, idd2n, idd3n)
    if kind == "micron":
        # worst-case background, spec-rate ACT/PRE, RD/WR stacked on top
        i_bg = jnp.where(pd == 0.0, idd3n, i_low)
        charge = i_bg * dt
        charge = charge + active * any_act * q_act * dt / _T.tRC
        charge = charge + is_rd * idd4r * burst + is_wr * idd4w * burst
    else:                             # drampower: actual timing
        i_bg = jnp.where(
            pd == 0.0, idd2n + (idd3n - idd2n) * open_banks / 8.0, i_low)
        charge = i_bg * dt
        charge = charge + is_act * q_act
        charge = charge + is_rd * (idd4r - i_bg) * burst
        charge = charge + is_wr * (idd4w - i_bg) * burst
    charge = charge + is_ref * (idd5b - idd2n) * _T.tRFC
    return charge * w


_CHARGE_FNS = {
    kind: (lambda planes, _, prm, kind=kind:
           _masked_charge(kind, *planes, [prm(k) for k in range(_N_IDD)]))
    for kind in ("micron", "drampower")}


def baseline_energy_pallas(kind: str, planes: dict, any_act, table,
                           block_n: int = BLOCK_N,
                           interpret: bool | None = None,
                           cells=None,
                           grid_layout: str = "vti") -> jax.Array:
    """(T, V) masked charge matrix of one baseline physics.  ``planes``
    maps :data:`PLANES` to (T, N) f32 arrays; ``any_act`` is (T,) f32;
    ``table`` is the stacked (V, K) datasheet matrix.  Passing ``cells``
    (the (T, N) structural cell index) switches to the surface reduction
    and returns the (T, V, CELLS) charge decomposition.  ``grid_layout``
    picks the grid-major order (``kernels.common.grid_maps``) — pure
    scheduling, identical partial sums either way."""
    if interpret is None:
        interpret = interpret_default()
    args = [planes[n].astype(jnp.float32) for n in PLANES]
    args.append(jnp.broadcast_to(any_act.astype(jnp.float32)[:, None],
                                 args[0].shape))
    return energy_grid_call(_CHARGE_FNS[kind], args, table,
                            name=(f"{kind}_energy" if cells is None
                                  else f"{kind}_surface"), cells=cells,
                            n_cells=N_SURFACE_CELLS, block_n=block_n,
                            interpret=interpret, grid_layout=grid_layout)
