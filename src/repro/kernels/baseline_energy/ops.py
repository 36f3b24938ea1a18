"""Jitted assembler for the fused baseline (Micron / DRAMPower) path:
builds the per-command structural planes from a padded TraceBatch and runs
the (vendors, traces, blocks)-gridded baseline energy kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.dram import ACT, N_BANKS, N_ROW_BANDS, RD, REF, WR, \
    CommandTrace
from repro.core.energy_model import (structural_state, surface_cells,
                                     surface_cycles)
from repro.kernels.baseline_energy.baseline_energy import \
    baseline_energy_pallas
from repro.kernels.common import interpret_default, pad_batch, pad_carry


@functools.partial(jax.jit,
                   static_argnames=("kind", "surface", "block_n",
                                    "interpret", "grid_layout"))
def _baseline_charge_matrix(trace: CommandTrace, weight,
                            tiled: CommandTrace, w_tiled, table, carry,
                            kind: str, surface: bool, block_n: int,
                            interpret: bool, grid_layout: str):
    t = trace.cmd.shape[0]
    st = jax.vmap(structural_state)(tiled, carry)
    planes = {
        "dt": tiled.dt.astype(jnp.float32),
        "is_rd": (tiled.cmd == RD).astype(jnp.float32),
        "is_wr": (tiled.cmd == WR).astype(jnp.float32),
        "is_act": (tiled.cmd == ACT).astype(jnp.float32),
        "is_ref": (tiled.cmd == REF).astype(jnp.float32),
        "open_banks": jnp.sum(st.open_before.astype(jnp.float32), axis=2),
        "pd": st.bg_state.astype(jnp.float32),
        "w": w_tiled.astype(jnp.float32),
    }
    any_act = jnp.any(tiled.cmd == ACT, axis=1)
    if carry is not None:
        any_act = any_act | carry.any_act     # the whole trace's ACTs
    any_act = any_act.astype(jnp.float32)
    if surface:
        charge = baseline_energy_pallas(kind, planes, any_act, table,
                                        block_n=block_n, interpret=interpret,
                                        cells=jax.vmap(surface_cells)(tiled),
                                        grid_layout=grid_layout)[:t]
        return (charge.reshape(t, -1, N_BANKS, N_ROW_BANDS),
                jax.vmap(surface_cycles)(trace, weight))
    charge = baseline_energy_pallas(kind, planes, any_act, table,
                                    block_n=block_n, interpret=interpret,
                                    grid_layout=grid_layout)[:t]
    cycles = jnp.sum(trace.dt * weight.astype(jnp.int32), axis=1,
                     dtype=jnp.int32)
    return charge, cycles


def baseline_charge_matrix(trace: CommandTrace, weight, table, kind: str, *,
                           surface: bool = False, block_n: int | None = None,
                           interpret: bool | None = None,
                           grid_layout: str | None = None, carry=None):
    """Masked charge of every (trace, vendor) pair for one baseline kind
    -> ``((T, V) charge in mA*cycles, (T,) masked cycles)``, or with
    ``surface=True`` the per-(bank, row-band) structural decomposition
    ``((T, V, 8, N_ROW_BANDS) charge, (T, 8, N_ROW_BANDS) cycles)``;
    ``carry`` the rows' segment carries (None for whole traces).
    ``block_n``/``grid_layout`` default to the autotuner's committed
    winner for this (backend, shape-bucket)
    (``kernels.autotune.best_config``)."""
    if interpret is None:
        interpret = interpret_default()
    if block_n is None or grid_layout is None:
        from repro.kernels import autotune
        cfg = autotune.best_config("baseline_energy", trace.cmd.shape[0],
                                   trace.cmd.shape[1])
        block_n = cfg["block_n"] if block_n is None else block_n
        grid_layout = (cfg["layout"] if grid_layout is None
                       else grid_layout)
    tiled, w_tiled = pad_batch(trace, weight, block_n)
    carry = pad_carry(carry, w_tiled.shape[0])
    return _baseline_charge_matrix(trace, weight, tiled, w_tiled, table,
                                   carry, kind, surface, block_n, interpret,
                                   grid_layout)
