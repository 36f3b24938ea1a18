"""Jitted assembler for the fused (traces x vendors) VAMPIRE energy path.

:func:`batched_charge_matrix` is the single entry point both consumers of
``impl='pallas'`` share — the estimation engine
(``repro.core.estimate_batch``) and the characterization fleet engine
(``repro.core.fleet``, where the "vendor" axis is the stacked module
params).  It runs the vectorized ``structural_state`` bookkeeping over the
padded batch, the param-independent feature kernel once, and the
per-vendor fused energy kernel over the (vendors, traces, blocks) grid.

``mode='distribution'`` support: passing ``ones_frac``/``toggle_frac``
skips the feature kernel and substitutes the expected per-command data
features (first-access toggles stay 0, matching
``energy_model.distribution_features``).  ``surface=True`` swaps the
scalar-sum energy kernel for the cell-reducing surface kernel
(``mode='surface'``: per-(bank, row-band) charge decomposition).

The old single-(trace, paramset) entry point ``trace_energy_kernel`` is a
shim onto the batched kernels (a (1, 1) grid)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.dram import (ACT, LINE_BITS, N_BANKS, N_ROW_BANDS, REF,
                             CommandTrace)
from repro.core.energy_model import (EnergyReport, N_SURFACE_CELLS,
                                     PowerParams, _report, structural_state,
                                     surface_cells, surface_cycles)
from repro.kernels.common import interpret_default, pad_batch, pad_carry
from repro.kernels.vampire_energy.vampire_energy import (
    batched_energy_pallas, batched_features_pallas, pack_params)


@functools.partial(jax.jit,
                   static_argnames=("surface", "block_n", "interpret",
                                    "grid_layout"))
def _vampire_charge_matrix(trace: CommandTrace, weight,
                           tiled: CommandTrace, w_tiled,
                           stacked: PowerParams, ones_frac, toggle_frac,
                           carry, surface: bool, block_n: int,
                           interpret: bool, grid_layout: str):
    t = trace.cmd.shape[0]
    st = jax.vmap(structural_state)(tiled, carry)
    if ones_frac is None:
        # measured-data modes: the fused popcount/toggle feature kernel
        # over the whole batch's data stream, once
        tmask = (st.has_prev & st.is_rw).astype(jnp.float32)
        ones, togg = batched_features_pallas(
            tiled.data, st.prev_data, tmask, block_n=block_n,
            interpret=interpret)
    else:
        # no-data-trace mode: expected fractions replace the data features
        def per_trace(frac):
            frac = jnp.broadcast_to(jnp.asarray(frac, jnp.float32), (t,))
            return jnp.pad(frac, (0, w_tiled.shape[0] - t))[:, None]
        ones = jnp.where(st.is_rw, per_trace(ones_frac) * LINE_BITS, 0.0)
        togg = jnp.where(st.is_rw & st.has_prev,
                         per_trace(toggle_frac) * LINE_BITS, 0.0)

    # the per-command structural ACT factor of every vendor: the (bank,
    # row-band) gather happens HERE (vectorized jnp bookkeeping), so the
    # kernel sees a plain (V, T, N) multiply plane
    cells = jax.vmap(surface_cells)(tiled)                       # (T, N)
    surf = stacked.act_surface.reshape(-1, N_SURFACE_CELLS)[:, cells]
    bank_bits = jnp.left_shift(1, jnp.arange(N_BANKS, dtype=jnp.int32))
    feats = {
        "ones": ones, "togg": togg,
        "op": st.op, "mode": st.il_mode,
        "dt": tiled.dt.astype(jnp.float32),
        "is_rw": st.is_rw.astype(jnp.float32),
        "is_act": (tiled.cmd == ACT).astype(jnp.float32),
        "is_ref": (tiled.cmd == REF).astype(jnp.float32),
        "pd": st.bg_state.astype(jnp.float32),
        "row_ones": st.row_ones.astype(jnp.float32),
        "w": w_tiled.astype(jnp.float32),
        "bank": tiled.bank.astype(jnp.int32),
        "open_bits": jnp.sum(jnp.where(st.open_before, bank_bits, 0),
                             axis=-1, dtype=jnp.int32),
        "surf": surf.astype(jnp.float32),                        # (V, T, N)
    }
    table = pack_params(stacked)
    if surface:
        charge = batched_energy_pallas(feats, table, block_n=block_n,
                                       interpret=interpret, cells=cells,
                                       grid_layout=grid_layout)[:t]
        return (charge.reshape(t, -1, N_BANKS, N_ROW_BANDS),
                jax.vmap(surface_cycles)(trace, weight))
    charge = batched_energy_pallas(feats, table, block_n=block_n,
                                   interpret=interpret,
                                   grid_layout=grid_layout)[:t]
    cycles = jnp.sum(trace.dt * weight.astype(jnp.int32), axis=1,
                     dtype=jnp.int32)
    return charge, cycles


def batched_charge_matrix(trace: CommandTrace, weight, stacked: PowerParams,
                          *, ones_frac=None, toggle_frac=None,
                          surface: bool = False, block_n: int | None = None,
                          interpret: bool | None = None,
                          grid_layout: str | None = None, carry=None):
    """Masked charge of every (trace, paramset) pair through the fused
    kernels -> ``((T, V) charge in mA*cycles, (T,) masked cycles)``, or
    with ``surface=True`` the structural decomposition
    ``((T, V, 8, N_ROW_BANDS) charge, (T, 8, N_ROW_BANDS) cycles)``.

    ``trace``/``weight`` are a padded TraceBatch's (T, N) fields;
    ``stacked`` carries a leading paramset axis; ``carry`` the rows'
    segment carries (``energy_model.SegmentCarry``, None for whole
    traces), which seed ``structural_state``.  ``interpret`` resolves
    per call (compiled on TPU, interpreted elsewhere) BEFORE entering the
    jitted body, so it participates in the jit cache key.  ``block_n`` /
    ``grid_layout`` likewise resolve per call: when not pinned by the
    caller, the autotuner's committed winner for this (backend,
    shape-bucket) applies (``kernels.autotune.best_config``), defaulting
    to the historical ``BLOCK_N``/vendor-major grid where untuned."""
    if interpret is None:
        interpret = interpret_default()
    if block_n is None or grid_layout is None:
        from repro.kernels import autotune
        cfg = autotune.best_config("vampire_energy", trace.cmd.shape[0],
                                   trace.cmd.shape[1])
        block_n = cfg["block_n"] if block_n is None else block_n
        grid_layout = (cfg["layout"] if grid_layout is None
                       else grid_layout)
    tiled, w_tiled = pad_batch(trace, weight, block_n)
    carry = pad_carry(carry, w_tiled.shape[0])
    return _vampire_charge_matrix(trace, weight, tiled, w_tiled, stacked,
                                  ones_frac, toggle_frac, carry, surface,
                                  block_n, interpret, grid_layout)


def trace_energy_kernel(trace: CommandTrace, pp: PowerParams) -> EnergyReport:
    """Legacy single-(trace, paramset) entry point, shimmed onto the
    batched kernel family as a (1 trace, 1 vendor) grid."""
    batch = jax.tree_util.tree_map(lambda x: x[None], trace)
    weight = jnp.ones((1, trace.n), jnp.float32)
    stacked = jax.tree_util.tree_map(lambda x: x[None], pp)
    charge, cycles = batched_charge_matrix(batch, weight, stacked)
    return _report(charge[0, 0], cycles[0])
