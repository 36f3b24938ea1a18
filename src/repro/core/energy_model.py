"""The shared DRAM energy integrator.

Both the ground-truth module simulation (`device_sim`) and the fitted VAMPIRE
model (`vampire`) evaluate command traces through this integrator; they differ
only in the parameter values (true per-module vs. fitted per-vendor) and in
the noise/unmodeled terms the simulator adds on top.

Semantics
---------
Each command owns a slot of ``dt`` DRAM clock cycles. During a slot the module
draws the *background* current implied by its bank/power-down state; commands
add charge on top:

* ``ACT``   — one activate+precharge pair's worth of charge (the paper shows
  the two cannot be measured separately; we assign the pair charge to the ACT
  and make PRE free), scaled by the row-address-ones structural factor.
* ``RD/WR`` — for ``tBURST`` cycles the module draws the data-dependent
  current ``I(mode, N_ones, N_toggles)`` (paper Eq. 2 / Table 5) times the
  per-bank structural factor, plus the I/O-driver current the measurement rig
  captures; the slot's background is credited back for those cycles.
* ``REF``   — a fixed charge above background per refresh burst.

The background current itself is resolved through a **state machine over
the idle/low-power lattice**, not a boolean: every command slot carries an
integer background state (``BG_*`` codes below) derived once per trace by
the same cumulative-event-index trick that tracks bank state, and the
state indexes a per-state current LUT (:func:`background_current`):

* ``BG_ACTIVE`` (0)   — powered up: ``i2n`` plus the open-bank deltas
  (precharge standby when all banks are closed, active standby otherwise).
* ``BG_PDN_FAST`` (1) — fast power-down (``PDE`` with all banks closed,
  DLL on): ``i_pd`` (datasheet ``IDD2P1``).
* ``BG_PDN_SLOW`` (2) — slow power-down (``PDE_SLOW``, DLL off):
  ``i_pd_slow`` (``IDD2P0``).
* ``BG_PDN_ACT`` (3)  — active power-down (``PDE`` while any bank is
  open; the open state is frozen until ``PDX``): ``i_actpd`` (``IDD3P``).
* ``BG_SR`` (4)       — self-refresh (``SRE``/``SRX``): ``i_sr``
  (``IDD6``).  Refresh is internal while in this state, so a trace in
  self-refresh owes no ``REF`` commands (and may not issue any —
  ``dram.validate_low_power_transitions``).

Entry commands (``PDE``/``PDE_SLOW``/``SRE``) and exits (``PDX``/``SRX``)
bill their own slot at the state in force BEFORE them: the entry slot is
still at the powered-up rate, the dwell rides on the slots after it, and
the exit slot is the last one billed at the low-power rate.

Charge is accumulated in mA x cycles; energy = charge * tCK * VDD.

Two implementations are provided with identical semantics:

* :func:`trace_energy_scan` — `lax.scan` command-by-command oracle.
* :func:`trace_energy_vectorized` — bank state via cumulative max over event
  indices, data dependency via popcount/XOR, everything fused elementwise.
  This is the production path (it is what makes 1e7+ command traces cheap)
  and is cross-checked against the oracle in tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram
from repro.core.dram import (ACT, PRE, PREA, RD, WR, REF, PDE, PDX,
                             PDE_SLOW, SRE, SRX,
                             IL_NONE, IL_COL, IL_BANK, IL_BANKCOL,
                             LINE_BITS, N_BANKS, N_ROW_BANDS, TIMING,
                             TCK_NS, VDD, CommandTrace, line_ones,
                             line_toggles, popcount_u32, row_band)

# flattened (bank, row-band) cell count of the structural-variation surface
N_SURFACE_CELLS = N_BANKS * N_ROW_BANDS

# ---------------------------------------------------------------------------
# The background-state lattice (see the module docstring).  Code 0 is the
# powered-up state, so a trace with no low-power commands carries an
# all-zero state vector and bills exactly as before the lattice existed.
# ---------------------------------------------------------------------------
BG_ACTIVE = 0     # powered up: i2n + open-bank deltas
BG_PDN_FAST = 1   # fast power-down (IDD2P1): i_pd
BG_PDN_SLOW = 2   # slow power-down, DLL off (IDD2P0): i_pd_slow
BG_PDN_ACT = 3    # active power-down, banks open (IDD3P): i_actpd
BG_SR = 4         # self-refresh (IDD6): i_sr
BG_STATE_NAMES = {BG_ACTIVE: "active", BG_PDN_FAST: "pdn_fast",
                  BG_PDN_SLOW: "pdn_slow", BG_PDN_ACT: "pdn_active",
                  BG_SR: "self_refresh"}


def background_current(pp: "PowerParams", bg_state, i_up):
    """The per-state background-current LUT: ``bg_state`` (int codes above)
    gathered against the low-power leaves of ``pp``; ``i_up`` is the
    powered-up current (``i2n`` + open-bank deltas), supplied by the caller
    because it is the only state whose current is trace-dependent.  All
    three impls (vectorized, reference scan, both Pallas kernel families)
    resolve the background through this one shape."""
    i_low = jnp.where(bg_state == BG_PDN_FAST, pp.i_pd,
                      jnp.where(bg_state == BG_PDN_SLOW, pp.i_pd_slow,
                                jnp.where(bg_state == BG_PDN_ACT,
                                          pp.i_actpd, pp.i_sr)))
    return jnp.where(bg_state == BG_ACTIVE, i_up, i_low)


class DataOps(NamedTuple):
    """The two data-stream reductions of the feature pass — per-line
    popcount and bus-XOR toggle count — as injectable callables: the seam
    that isolates the O(N x 512 bit) work from the index bookkeeping.
    ``extract_structural_features`` takes one, so a SINGLE-trace feature
    pass can run through the ``kernels/popcount`` / ``kernels/toggle``
    Pallas ops (:func:`kernel_data_ops`; the parity suite pins it equal
    to the jnp default).  The batched ``impl='pallas'`` path does not
    come through here — it fuses both reductions into one kernel over the
    whole batch (``kernels/vampire_energy.batched_features_pallas``)."""
    line_ones: object    # (N, 16) uint32 -> (N,) counts
    line_toggles: object  # ((N, 16), (N, 16)) uint32 -> (N,) counts


JNP_DATA_OPS = DataOps(line_ones=line_ones, line_toggles=line_toggles)


def kernel_data_ops() -> DataOps:
    """The Pallas-kernel-backed :class:`DataOps` (``kernels/popcount`` +
    ``kernels/toggle``), resolved lazily so importing this module never
    pulls in the kernel stack."""
    from repro.kernels.popcount import ops as pc_ops
    from repro.kernels.toggle import ops as tg_ops
    return DataOps(line_ones=pc_ops.line_ones, line_toggles=tg_ops.line_toggles)


class PowerParams(NamedTuple):
    """Everything the integrator needs, as JAX arrays (so params are a pytree
    and fitting can be jitted/vmapped over modules)."""
    datadep: jax.Array            # (4 modes, 2 ops, 3 coeffs) mA
    i2n: jax.Array                # () mA   background, all banks closed
    bank_open_delta: jax.Array    # (8,) mA added per open bank (structural)
    bank_read_factor: jax.Array   # (8,) multiplicative on read current
    bank_write_factor: jax.Array  # (8,)
    q_actpre: jax.Array           # () mA*cycles charge per ACT(+PRE) pair
    row_ones_slope: jax.Array     # () fractional act-charge per row-addr one
    q_ref: jax.Array              # () mA*cycles above background per REF
    i_pd: jax.Array               # () mA background in fast power-down
    io_read_ma_per_one: jax.Array   # () rig-visible I/O driver current
    io_write_ma_per_zero: jax.Array # ()
    ones_quad: jax.Array          # () unmodeled curvature (sim-only; 0 in fit)
    # (8, N_ROW_BANDS) structural ACT factor per (bank, row band); band 0
    # == 1.0.  Defaulted (neutral, np so importing this module never
    # initializes a jax backend) so parameter sets pickled before the
    # surface existed keep unpickling.
    act_surface: jax.Array = np.ones((N_BANKS, N_ROW_BANDS), np.float32)
    # the rest of the background-state LUT (fast power-down i_pd sits
    # above for leaf-order compatibility).  Defaulted (np scalars) so
    # parameter sets serialized before the state lattice keep loading;
    # traces without the new low-power commands never read them.
    i_pd_slow: jax.Array = np.float32(0.0)  # () mA slow PDN, DLL off (IDD2P0)
    i_actpd: jax.Array = np.float32(0.0)    # () mA active power-down (IDD3P)
    i_sr: jax.Array = np.float32(0.0)       # () mA self-refresh (IDD6)

    @property
    def i3n(self):
        return self.i2n + jnp.sum(self.bank_open_delta)


def zeros_like_params() -> PowerParams:
    z = jnp.zeros(())
    return PowerParams(jnp.zeros((4, 2, 3)), z, jnp.zeros(8), jnp.ones(8),
                       jnp.ones(8), z, z, z, z, z, z, z,
                       jnp.ones((N_BANKS, N_ROW_BANDS)), z, z, z)


class TraceFeatures(NamedTuple):
    """Per-command derived features (vectorized preprocessing)."""
    is_rw: jax.Array       # (N,) bool
    op: jax.Array          # (N,) int32: 0 read / 1 write (valid where is_rw)
    il_mode: jax.Array     # (N,) int32 in [0,4)
    ones: jax.Array        # (N,) int32
    toggles: jax.Array     # (N,) int32 (global bus, vs previous RD/WR)
    open_banks: jax.Array  # (N,) float32: number of open banks (weighted)
    bg_delta_sum: jax.Array  # (N,) float32: sum of bank_open_delta over open
    bg_state: jax.Array    # (N,) int32 background-state code (BG_*)
    row_ones: jax.Array    # (N,) int32 popcount of row addr (ACT rows)


class StructuralFeatures(NamedTuple):
    """The parameter-independent part of feature extraction: everything
    derivable from the trace alone. Extracting these ONCE per trace and
    finalizing per parameter set is what lets the batched estimation engine
    amortize the popcount/XOR/cummax work across vendors (the only
    param-dependent feature is the open-bank background sum)."""
    is_rw: jax.Array         # (N,) bool
    op: jax.Array            # (N,) int32
    il_mode: jax.Array       # (N,) int32 in [0,4)
    ones: jax.Array          # (N,) int32
    toggles: jax.Array       # (N,) int32
    open_before: jax.Array   # (N, 8) bool: bank open state before each cmd
    bg_state: jax.Array      # (N,) int32 background-state code (BG_*)
    row_ones: jax.Array      # (N,) int32
    has_prev: jax.Array      # (N,) bool: a RD/WR came before (carry included)


# ---------------------------------------------------------------------------
# Segment carry: a trace cut into rows keeps its whole-trace semantics
# ---------------------------------------------------------------------------
class SegmentCarry(NamedTuple):
    """The state a trace segment inherits from the commands before it.

    Every field is the last event of its kind before the segment's first
    command, or a flag derived from such events, so the carries of all of
    a trace's segments follow from the trace alone
    (:func:`events_before`, :func:`segment_carry`) and need no estimate
    of earlier segments.  ``any_act`` is the one whole-trace fact: the
    Micron baseline bills ACT/PRE at the specification rate whenever the
    trace issues any ACT.
    The carry is parameter-independent.  :func:`empty_carry` (nothing
    before) is the identity: every path then gives the unsegmented
    answer.  Batched, every leaf has a leading row axis."""
    open: jax.Array          # (8,) bool: bank open
    bg_mode: jax.Array       # () int32 entry kind: BG_ACTIVE/_PDN_FAST/
    #                          _PDN_SLOW/_SR (fast vs active from ``open``)
    has_prev: jax.Array      # () bool: a RD/WR came before
    prev_data: jax.Array     # (16,) uint32: its line (0 if none)
    prev_bank: jax.Array     # () int32: its bank (-1 if none)
    last_col: jax.Array      # (8,) int32: each bank's last RD/WR column
    any_act: jax.Array       # () bool: the whole trace issues an ACT


def empty_carry(rows: int | None = None) -> SegmentCarry:
    """The carry of a trace's first segment (numpy leaves; with ``rows``
    a leading row axis)."""
    lead = () if rows is None else (rows,)
    return SegmentCarry(
        open=np.zeros(lead + (N_BANKS,), np.bool_),
        bg_mode=np.full(lead, BG_ACTIVE, np.int32),
        has_prev=np.zeros(lead, np.bool_),
        prev_data=np.zeros(lead + (dram.LINE_WORDS,), np.uint32),
        prev_bank=np.full(lead, -1, np.int32),
        last_col=np.full(lead + (N_BANKS,), -1, np.int32),
        any_act=np.zeros(lead, np.bool_))


def last_before(pos: np.ndarray, at) -> np.ndarray:
    """The last of the sorted event positions ``pos`` strictly before each
    of ``at`` (-1 if none; ``at`` of any shape)."""
    at = np.asarray(at)
    if not len(pos):
        return np.full(at.shape, -1)
    j = np.searchsorted(pos, at) - 1
    return np.where(j >= 0, pos[np.maximum(j, 0)], -1)


def last_before_per_bank(pos: np.ndarray, bank: np.ndarray, at
                         ) -> np.ndarray:
    """:func:`last_before` per bank -> (len(at), 8): ``pos`` are events'
    positions, ``bank`` the whole trace's bank field."""
    of = bank[pos].astype(np.uint8)
    by_bank = pos[np.argsort(of, kind="stable")]   # each bank's, in order
    ends = np.cumsum(np.bincount(of, minlength=N_BANKS))
    return np.stack([last_before(by_bank[e - c:e], at) for c, e in
                     zip(np.bincount(of, minlength=N_BANKS), ends)], axis=1)


class EventsBefore(NamedTuple):
    """Per segment start, the position in the whole trace (-1: none) of
    the last event of each kind strictly before it: what both the
    integrator's :class:`SegmentCarry` and the lint's carry are made from
    (:func:`segment_carry`, ``trace_lint.lint_carry``)."""
    act: np.ndarray          # (k, 8) per bank: ACT
    close: np.ndarray        # (k, 8) per bank: PRE, or PREA
    rd: np.ndarray           # (k, 8) per bank: RD
    wr: np.ndarray           # (k, 8) per bank: WR
    ref: np.ndarray          # (k,)
    pde: np.ndarray          # (k,) fast (or active) power-down entry
    pde_slow: np.ndarray     # (k,)
    pdx: np.ndarray          # (k,)
    pdx_slow: np.ndarray     # (k,) a PDX whose entry was slow
    sre: np.ndarray          # (k,)
    srx: np.ndarray          # (k,)
    act4: np.ndarray         # (k, 4) the last four ACTs, oldest first
    any_act: np.ndarray      # (k,) bool: the whole trace issues an ACT


def events_before(trace: CommandTrace, starts) -> EventsBefore:
    """One scan of ``trace`` (host numpy): per event kind its sorted
    positions, searched at every start."""
    cmd = np.asarray(trace.cmd)
    bank = np.asarray(trace.bank)
    starts = np.asarray(starts, np.int64)
    pos = {c: np.flatnonzero(cmd == c)
           for c in (ACT, PRE, PREA, RD, WR, REF, PDE, PDE_SLOW, PDX, SRE,
                     SRX)}

    def last(c):
        return last_before(pos[c], starts)

    def per_bank(c):
        return last_before_per_bank(pos[c], bank, starts)

    # a PDX is slow when the power-down entry before it was
    pdx = pos[PDX]
    slow = last_before(pos[PDE_SLOW], pdx) > last_before(pos[PDE], pdx)
    acts = np.concatenate([np.full(4, -1), pos[ACT]])
    n_acts = np.searchsorted(pos[ACT], starts)
    return EventsBefore(
        act=per_bank(ACT),
        close=np.maximum(per_bank(PRE), last(PREA)[:, None]),
        rd=per_bank(RD), wr=per_bank(WR), ref=last(REF), pde=last(PDE),
        pde_slow=last(PDE_SLOW), pdx=last(PDX),
        pdx_slow=last_before(pdx[slow], starts), sre=last(SRE),
        srx=last(SRX), act4=acts[n_acts[:, None] + np.arange(4)],
        any_act=np.full(len(starts), len(pos[ACT]) > 0))


def segment_carry(trace: CommandTrace, ev: EventsBefore) -> SegmentCarry:
    """The integrator's carry of every segment of ``trace`` whose
    :func:`events_before` is ``ev`` (host numpy, leading axis the
    segments).  The background state follows the integrator's lattice
    (self-refresh before power-down; the latest entry gives the
    power-down kind)."""
    bank = np.asarray(trace.bank)
    out = empty_carry(len(ev.ref))._asdict()
    out["open"][:] = ev.act > ev.close
    rw_b = np.maximum(ev.rd, ev.wr)
    out["last_col"][:] = np.where(
        rw_b >= 0, np.asarray(trace.col)[np.maximum(rw_b, 0)], -1)
    in_pdn = np.maximum(ev.pde, ev.pde_slow) > ev.pdx
    out["bg_mode"][:] = np.where(
        ev.sre > ev.srx, BG_SR,
        np.where(in_pdn, np.where(ev.pde >= ev.pde_slow, BG_PDN_FAST,
                                  BG_PDN_SLOW), BG_ACTIVE))
    prev = rw_b.max(axis=1)
    has = prev >= 0
    out["has_prev"][:] = has
    out["prev_data"][has] = np.asarray(trace.data)[prev[has]]
    out["prev_bank"][has] = bank[prev[has]]
    out["any_act"][:] = ev.any_act
    return SegmentCarry(**out)


# ---------------------------------------------------------------------------
# Vectorized feature extraction
# ---------------------------------------------------------------------------
#: "no event before": the exclusive cummaxes below start from this, a
#: carried event sits at index -1, the segment's own at 0..N-1
_NONE = -2


def _exclusive_cummax(x: jax.Array, seed=_NONE) -> jax.Array:
    """cummax over axis 0, exclusive (state *before* each element),
    starting from ``seed`` (broadcast against one element)."""
    shifted = jnp.concatenate(
        [jnp.full_like(x[:1], _NONE), jax.lax.cummax(x, axis=0)[:-1]], axis=0)
    return jnp.maximum(shifted, seed)


def _carried(flag) -> jax.Array:
    """A carried event's seed: index -1 where ``flag`` holds."""
    return jnp.where(flag, -1, _NONE)


#: a packed key is ``position << 16 | half``: a 16-bit half of a value
#: below the position (0 the carry, i + 1 command i) of its event
_HALF = 16


def gather_free(n: int) -> bool:
    """Whether :func:`structural_state` reads earlier commands' fields at
    row length ``n`` with packed-key cummaxes and one-hot selects, no
    gather: every position 0..n fits above a half in a non-negative
    int32.  Longer rows gather."""
    return n + 1 < 1 << (31 - _HALF)


def _halves(x) -> jax.Array:
    """A 32-bit array's low and high 16 bits -> (..., 2) int32."""
    u = jax.lax.bitcast_convert_type(jnp.asarray(x), jnp.uint32)
    return jnp.stack([u & 0xFFFF, u >> _HALF], axis=-1).astype(jnp.int32)


def _join(key: jax.Array, dtype) -> jax.Array:
    """The value whose :func:`_halves` two packed keys (..., 2) carry, in
    ``dtype``; 0 where the keys are ``_NONE``."""
    h = jnp.where(key >= 0, key & 0xFFFF, 0).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(h[..., 0] | h[..., 1] << _HALF, dtype)


def _earlier_rw_gathered(trace: CommandTrace, carry: SegmentCarry, is_rw,
                         rw_in_bank):
    """:func:`_earlier_rw_packed` by index and gather, for any length."""
    bank = trace.bank
    idx = jnp.arange(bank.shape[0], dtype=jnp.int32)
    prev_rw = _exclusive_cummax(jnp.where(is_rw, idx, _NONE),
                                _carried(carry.has_prev))             # (N,)
    has_prev = prev_rw > _NONE
    prev_rw_c = jnp.maximum(prev_rw, 0)
    prev_data = jnp.where(
        (prev_rw >= 0)[:, None], trace.data[prev_rw_c],
        jnp.where((prev_rw == -1)[:, None], carry.prev_data, 0)
    ).astype(trace.data.dtype)                                        # (N,16)
    prev_bank = jnp.where(prev_rw >= 0, bank[prev_rw_c],
                          jnp.where(prev_rw == -1, carry.prev_bank, -1))

    last_rw_in_bank = _exclusive_cummax(
        jnp.where(rw_in_bank, idx[:, None], _NONE),
        _carried(jnp.asarray(carry.last_col) >= 0))
    this_bank_last = jnp.take_along_axis(last_rw_in_bank, bank[:, None],
                                         axis=1)[:, 0]                # (N,)
    prev_col_same_bank = jnp.where(
        this_bank_last >= 0, trace.col[jnp.maximum(this_bank_last, 0)],
        jnp.where(this_bank_last == -1,
                  jnp.asarray(carry.last_col)[bank], -1))
    return prev_data, has_prev, prev_bank, prev_col_same_bank


def _earlier_rw_packed(trace: CommandTrace, carry: SegmentCarry, is_rw,
                       rw_in_bank):
    """Per command, from the RD/WRs before it (carry included): the
    previous one's line (0 if none), whether there is one, its bank, and
    the column of the last one in this command's bank (-1 if none).
    Every 32-bit field rides as two packed keys through one exclusive
    cummax; this command's bank picks its column by a one-hot select.
    Needs :func:`gather_free` of the length."""
    bank = trace.bank
    n = bank.shape[0]
    n_line = dram.LINE_WORDS + 1                 # the line's words, the bank
    last_col = jnp.asarray(carry.last_col)
    # 25 slots (the words, the bank, each bank's column): per command its
    # event and halves, and the carry's at position 0
    event = jnp.concatenate(
        [jnp.broadcast_to(is_rw[:, None], (n, n_line)), rw_in_bank], axis=1)
    half = jnp.concatenate(
        [_halves(trace.data), _halves(bank)[:, None],
         jnp.broadcast_to(_halves(trace.col)[:, None], (n, N_BANKS, 2))],
        axis=1)                                                   # (N,25,2)
    carried = jnp.concatenate([jnp.broadcast_to(carry.has_prev, (n_line,)),
                               last_col >= 0])
    carried_half = jnp.concatenate([_halves(carry.prev_data),
                                    _halves(carry.prev_bank)[None],
                                    _halves(last_col)])
    # the latest position wins the cummax and brings its half along
    pos = jnp.arange(1, n + 1, dtype=jnp.int32)[:, None, None] << _HALF
    key = _exclusive_cummax(
        jnp.where(event[..., None], pos | half, _NONE),
        jnp.where(carried[:, None], carried_half, _NONE))          # (N,25,2)
    has_prev = key[:, 0, 0] >= 0
    prev_data = _join(key[:, :dram.LINE_WORDS], trace.data.dtype)
    prev_bank = jnp.where(has_prev, _join(key[:, dram.LINE_WORDS],
                                          jnp.int32), -1)
    # the bank as the gather indexes: a negative one counts from the
    # end, and one past either end reads none
    at = jax.nn.one_hot(jnp.where(bank < 0, bank + N_BANKS, bank), N_BANKS,
                        dtype=jnp.bool_)
    in_bank = jnp.max(jnp.where(at[..., None], key[:, n_line:], _NONE),
                      axis=1)                                         # (N,2)
    prev_col_same_bank = jnp.where(in_bank[:, 0] >= 0,
                                   _join(in_bank, jnp.int32), -1)
    return prev_data, has_prev, prev_bank, prev_col_same_bank


class StructuralState(NamedTuple):
    """The index-bookkeeping half of the structural pass: everything the
    trace alone determines EXCEPT the O(N x 512 bit) data reductions.
    Splitting it out lets the Pallas impl run the same state machine and
    feed ``prev_data`` to its fused feature kernel over a whole batch."""
    is_rw: jax.Array        # (N,) bool
    op: jax.Array           # (N,) int32
    il_mode: jax.Array      # (N,) int32 in [0,4)
    open_before: jax.Array  # (N, 8) bool
    bg_state: jax.Array     # (N,) int32 background-state code (BG_*)
    row_ones: jax.Array     # (N,) int32
    prev_data: jax.Array    # (N, 16) uint32: previous RD/WR line (0 if none)
    has_prev: jax.Array     # (N,) bool


def structural_state(trace: CommandTrace,
                     carry: SegmentCarry | None = None) -> StructuralState:
    """The index bookkeeping of one trace (or one segment of one, with the
    ``carry`` of the commands before it; None is :func:`empty_carry`).
    Every exclusive cummax starts from the carry: a carried event sits at
    index -1, before the segment's own.  Where :func:`gather_free` holds
    for the row length, nothing is gathered."""
    if carry is None:
        carry = empty_carry()
    cmd, bank = trace.cmd, trace.bank
    n = cmd.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    is_rw = (cmd == RD) | (cmd == WR)
    op = jnp.where(cmd == WR, 1, 0).astype(jnp.int32)

    # ---- bank open/closed state before each command -----------------------
    bank_oh = jax.nn.one_hot(bank, N_BANKS, dtype=jnp.bool_)  # (N,8)
    act_ev = (cmd == ACT)[:, None] & bank_oh
    pre_ev = ((cmd == PRE)[:, None] & bank_oh) | (cmd == PREA)[:, None]
    last_act = _exclusive_cummax(jnp.where(act_ev, idx[:, None], _NONE),
                                 _carried(carry.open))                 # (N,8)
    last_pre = _exclusive_cummax(jnp.where(pre_ev, idx[:, None], _NONE))
    open_before = last_act > last_pre                                  # (N,8)

    # ---- background-state lattice (power-down / self-refresh) -------------
    # Same cumulative-event-index trick as the bank state: the most recent
    # entry vs exit event before each slot decides the state; which ENTRY
    # is most recent decides the power-down flavor.  A fast entry with any
    # bank open is ACTIVE power-down — PDE freezes (not closes) the banks,
    # and since ACT/PRE are illegal inside power-down the per-slot
    # ``open_before`` equals the open state at entry.
    def last_of(code, carried_mode=None):
        seed = (_NONE if carried_mode is None
                else _carried(carry.bg_mode == carried_mode))
        return _exclusive_cummax(jnp.where(cmd == code, idx, _NONE), seed)

    last_pdf = last_of(PDE, BG_PDN_FAST)
    last_pds = last_of(PDE_SLOW, BG_PDN_SLOW)
    last_pdx = last_of(PDX)
    last_sre = last_of(SRE, BG_SR)
    last_srx = last_of(SRX)
    in_pdn = jnp.maximum(last_pdf, last_pds) > last_pdx
    in_sr = last_sre > last_srx
    any_open = jnp.any(open_before, axis=1)
    pd_kind = jnp.where(last_pdf >= last_pds,
                        jnp.where(any_open, BG_PDN_ACT, BG_PDN_FAST),
                        BG_PDN_SLOW)
    bg_state = jnp.where(in_sr, BG_SR,
                         jnp.where(in_pdn, pd_kind, BG_ACTIVE)
                         ).astype(jnp.int32)

    # ---- previous RD/WR on the bus and in the bank (toggles, interleave) --
    earlier_rw = (_earlier_rw_packed if gather_free(n)
                  else _earlier_rw_gathered)
    prev_data, has_prev, prev_bank, prev_col_same_bank = earlier_rw(
        trace, carry, is_rw, is_rw[:, None] & bank_oh)

    # the previous RD/WR in this bank is the previous one on the bus when
    # the banks match, so one column comparison serves both branches
    same_bank = has_prev & (prev_bank == bank)
    same_col_in_bank = prev_col_same_bank == trace.col
    il_mode = jnp.where(
        ~has_prev, IL_NONE,
        jnp.where(same_bank,
                  jnp.where(same_col_in_bank, IL_NONE, IL_COL),
                  jnp.where(same_col_in_bank, IL_BANK, IL_BANKCOL)))
    il_mode = il_mode.astype(jnp.int32)

    row_ones = popcount_u32(trace.row.astype(jnp.uint32))
    return StructuralState(is_rw, op, il_mode, open_before, bg_state,
                           row_ones, prev_data, has_prev)


def extract_structural_features(trace: CommandTrace,
                                data_ops: DataOps = JNP_DATA_OPS,
                                carry: SegmentCarry | None = None
                                ) -> StructuralFeatures:
    """The parameter-independent feature pass (see StructuralFeatures).

    ``data_ops`` injects the popcount/toggle reductions — pure jnp by
    default, the Pallas kernel ops under the ``impl`` registry;
    ``carry`` is the segment's (:func:`structural_state`)."""
    st = structural_state(trace, carry)
    ones = data_ops.line_ones(trace.data)
    toggles = jnp.where(st.has_prev & st.is_rw,
                        data_ops.line_toggles(trace.data, st.prev_data), 0)
    return StructuralFeatures(st.is_rw, st.op, st.il_mode, ones, toggles,
                              st.open_before, st.bg_state, st.row_ones,
                              st.has_prev)


def finalize_features(sf: StructuralFeatures,
                      pp: PowerParams) -> TraceFeatures:
    """Attach the (cheap) parameter-dependent features to a structural
    pass: the per-command open-bank background-current sum."""
    bg_delta_sum = jnp.sum(jnp.where(sf.open_before, pp.bank_open_delta, 0.0),
                           axis=1)
    open_banks = jnp.sum(sf.open_before.astype(jnp.float32), axis=1)
    return TraceFeatures(sf.is_rw, sf.op, sf.il_mode, sf.ones, sf.toggles,
                         open_banks, bg_delta_sum, sf.bg_state,
                         sf.row_ones)


def extract_features(trace: CommandTrace, pp: PowerParams) -> TraceFeatures:
    return finalize_features(extract_structural_features(trace), pp)


def distribution_features(sf: StructuralFeatures, ones_frac,
                          toggle_frac) -> StructuralFeatures:
    """The paper's no-data-trace mode: replace the measured per-command data
    features with expected ones/toggle fractions. First-access semantics
    match ``extract_structural_features``: the first RD/WR on the bus has no
    previous burst to toggle against, so its expected toggle count is 0
    regardless of ``toggle_frac`` (a segment's carry may supply one). The
    single source of truth for this rule — the serial and batched
    estimators both go through it."""
    has_prev = sf.has_prev
    ones = jnp.where(sf.is_rw,
                     jnp.asarray(ones_frac, jnp.float32) * LINE_BITS, 0.0)
    togg = jnp.where(sf.is_rw & has_prev,
                     jnp.asarray(toggle_frac, jnp.float32) * LINE_BITS, 0.0)
    return sf._replace(ones=ones.astype(jnp.float32),
                       toggles=togg.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Charge accumulation from features (shared by sim and model)
# ---------------------------------------------------------------------------
def rw_current(pp: PowerParams, op, il_mode, ones, toggles, bank):
    """Data-dependent RD/WR current (paper Eq. 2), incl. structural bank
    factor and the rig-visible I/O driver current. All args broadcastable."""
    coeffs = pp.datadep[il_mode, op]                  # (..., 3)
    onesf = ones.astype(jnp.float32)
    togf = toggles.astype(jnp.float32)
    base = coeffs[..., 0] + coeffs[..., 1] * onesf + coeffs[..., 2] * togf
    # optional unmodeled curvature (ground-truth sim only; 0 when fitted)
    base = base + pp.ones_quad * coeffs[..., 1] * onesf * (
        onesf / dram.LINE_BITS - 0.5)
    factor = jnp.where(op == 0, pp.bank_read_factor[bank],
                       pp.bank_write_factor[bank])
    io = jnp.where(op == 0,
                   pp.io_read_ma_per_one * onesf,
                   pp.io_write_ma_per_zero * (dram.LINE_BITS - onesf))
    return base * factor + io


def integrate_charges(trace: CommandTrace, feats: TraceFeatures,
                      pp: PowerParams, i_rw: jax.Array) -> jax.Array:
    """The integrator: bank-state background over each command's slot,
    RD/WR burst crediting, ACT (+PRE pair) and REF charges — the
    fixed-shape form every ``impl`` shares.  ``i_rw`` is the
    data-dependent RD/WR current, supplied by the caller (``rw_current``
    on the vectorized path, the fused Pallas kernel on the ``pallas``
    path).  Returns per-command (N,) charges in mA*cycles; a dt=0 pad
    slot contributes exactly zero."""
    dt = trace.dt.astype(jnp.float32)
    i_bg = background_current(pp, feats.bg_state,
                              pp.i2n + feats.bg_delta_sum)
    charge = i_bg * dt

    # RD/WR burst charge above background
    burst = jnp.minimum(dt, float(TIMING.tBURST))
    charge = charge + jnp.where(feats.is_rw, (i_rw - i_bg) * burst, 0.0)

    # ACT (+PRE pair) charge with the row-address structural factor and the
    # per-(bank, row-band) structural surface (paper Section 6)
    act_q = pp.q_actpre * (1.0 + pp.row_ones_slope
                           * feats.row_ones.astype(jnp.float32))
    act_q = act_q * pp.act_surface[trace.bank, row_band(trace.row)]
    charge = charge + jnp.where(trace.cmd == ACT, act_q, 0.0)

    # REF charge above background
    charge = charge + jnp.where(trace.cmd == REF, pp.q_ref, 0.0)
    return charge


def charge_from_features(trace: CommandTrace, feats: TraceFeatures,
                         pp: PowerParams):
    """Per-command charge (mA*cycles). Returns (N,) charges."""
    i_rw = rw_current(pp, feats.op, feats.il_mode, feats.ones, feats.toggles,
                      trace.bank)
    return integrate_charges(trace, feats, pp, i_rw)


def masked_totals(trace: CommandTrace, weight: jax.Array,
                  charges: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Reduce per-command charges to (masked charge, masked cycles) under a
    validity/measurement mask — the shared tail of every fixed-shape
    batched evaluation (padding and setup slots carry weight 0)."""
    cycles = jnp.sum(trace.dt * weight.astype(jnp.int32), dtype=jnp.int32)
    return jnp.sum(charges * weight), cycles


# ---------------------------------------------------------------------------
# The structural-variation surface reduction (mode='surface'): the grouped
# twin of ``masked_totals``.  Every impl shares the same cell bookkeeping —
# a command belongs to the (bank, row-band) cell of its bank/row address —
# so the surfaces are parity-held across impls by construction, and summing
# a surface over its cells recovers the mode='mean' totals exactly.
# ---------------------------------------------------------------------------
def surface_cells(trace: CommandTrace) -> jax.Array:
    """(N,) flattened (bank, row-band) cell index of every command."""
    return trace.bank * N_ROW_BANDS + row_band(trace.row)


def surface_charge(trace: CommandTrace, weight: jax.Array,
                   charges: jax.Array) -> jax.Array:
    """Masked per-command charges grouped onto the structural surface ->
    (8, N_ROW_BANDS) mA*cycles.  A weight-0 (pad/setup) slot contributes
    exactly zero to its cell."""
    grouped = jax.ops.segment_sum(charges * weight, surface_cells(trace),
                                  num_segments=N_SURFACE_CELLS)
    return grouped.reshape(N_BANKS, N_ROW_BANDS)


def surface_cycles(trace: CommandTrace, weight: jax.Array) -> jax.Array:
    """Masked cycles grouped onto the surface -> (8, N_ROW_BANDS) int32
    (parameter-independent: shared across every vendor of a dispatch)."""
    grouped = jax.ops.segment_sum(trace.dt * weight.astype(jnp.int32),
                                  surface_cells(trace),
                                  num_segments=N_SURFACE_CELLS)
    return grouped.reshape(N_BANKS, N_ROW_BANDS).astype(jnp.int32)


class EnergyReport(NamedTuple):
    charge_ma_cycles: jax.Array
    cycles: jax.Array
    avg_current_ma: jax.Array
    energy_pj: jax.Array   # charge * tCK_ns * VDD  (mA*ns*V == pJ)
    time_ns: jax.Array


def _report(total_charge, total_cycles) -> EnergyReport:
    t_ns = total_cycles.astype(jnp.float32) * TCK_NS
    avg = total_charge / jnp.maximum(total_cycles.astype(jnp.float32), 1.0)
    return EnergyReport(total_charge, total_cycles, avg,
                        total_charge * TCK_NS * VDD, t_ns)


def scale_report(rep: EnergyReport, factor) -> EnergyReport:
    """Apply a multiplicative current factor to a report: charge, current,
    and energy scale together; the trace's duration does not."""
    return EnergyReport(rep.charge_ma_cycles * factor, rep.cycles,
                        rep.avg_current_ma * factor, rep.energy_pj * factor,
                        rep.time_ns)


@functools.partial(jax.jit, static_argnames=())
def trace_energy_vectorized(trace: CommandTrace, pp: PowerParams) -> EnergyReport:
    feats = extract_features(trace, pp)
    charges = charge_from_features(trace, feats, pp)
    return _report(jnp.sum(charges), trace.total_cycles())


def per_command_energy(trace: CommandTrace, pp: PowerParams) -> jax.Array:
    """(N,) per-command energy in pJ (vectorized path)."""
    feats = extract_features(trace, pp)
    charges = charge_from_features(trace, feats, pp)
    return charges * TCK_NS * VDD


# ---------------------------------------------------------------------------
# Scan oracle (identical semantics, sequential state machine)
# ---------------------------------------------------------------------------
class _ScanState(NamedTuple):
    bank_open: jax.Array        # (8,) bool
    # background ENTRY kind: BG_ACTIVE / BG_PDN_FAST / BG_PDN_SLOW / BG_SR;
    # the fast-vs-active distinction is resolved per step from bank_open
    # (matching the vectorized lattice's per-slot ``open_before``)
    bg_mode: jax.Array          # () int32
    prev_data: jax.Array        # (16,) uint32
    has_prev: jax.Array         # () bool
    prev_bank: jax.Array        # () int32
    last_col_in_bank: jax.Array # (8,) int32 (-1 = never)
    charge: jax.Array           # () float32


@jax.jit
def trace_charges_scan(trace: CommandTrace, pp: PowerParams,
                       carry: SegmentCarry | None = None) -> jax.Array:
    """(N,) per-command charges (mA*cycles) from the sequential oracle —
    the ``impl='reference'`` source for the surface decomposition.  A
    segment's ``carry`` is its initial state."""
    def step(s: _ScanState, x):
        cmd, bank, row, col, data, dt = x
        dtf = dt.astype(jnp.float32)
        bg_state = jnp.where(
            (s.bg_mode == BG_PDN_FAST) & jnp.any(s.bank_open),
            BG_PDN_ACT, s.bg_mode)
        i_bg = background_current(
            pp, bg_state,
            pp.i2n + jnp.sum(jnp.where(s.bank_open, pp.bank_open_delta, 0.0)))
        charge = i_bg * dtf

        is_rw = (cmd == RD) | (cmd == WR)
        op = jnp.where(cmd == WR, 1, 0)
        same_bank = s.has_prev & (s.prev_bank == bank)
        prev_col_b = s.last_col_in_bank[bank]
        il_mode = jnp.where(
            ~s.has_prev, IL_NONE,
            jnp.where(same_bank,
                      jnp.where(prev_col_b == col, IL_NONE, IL_COL),
                      jnp.where(prev_col_b == col, IL_BANK, IL_BANKCOL)))
        ones = line_ones(data)
        toggles = jnp.where(s.has_prev,
                            line_ones(jnp.bitwise_xor(data, s.prev_data)), 0)
        i_rw = rw_current(pp, op, il_mode, ones, toggles, bank)
        burst = jnp.minimum(dtf, float(TIMING.tBURST))
        charge = charge + jnp.where(is_rw, (i_rw - i_bg) * burst, 0.0)

        row_ones = jnp.sum(popcount_u32(row.astype(jnp.uint32)[None]))
        act_q = pp.q_actpre * (1.0 + pp.row_ones_slope * row_ones)
        act_q = act_q * pp.act_surface[bank, row_band(row)]
        charge = charge + jnp.where(cmd == ACT, act_q, 0.0)
        charge = charge + jnp.where(cmd == REF, pp.q_ref, 0.0)

        bank_oh = jax.nn.one_hot(bank, N_BANKS, dtype=jnp.bool_)
        bank_open = jnp.where(cmd == ACT, s.bank_open | bank_oh, s.bank_open)
        bank_open = jnp.where(cmd == PRE, bank_open & ~bank_oh, bank_open)
        bank_open = jnp.where(cmd == PREA, jnp.zeros_like(bank_open), bank_open)
        bg_mode = s.bg_mode
        bg_mode = jnp.where(cmd == PDE, BG_PDN_FAST, bg_mode)
        bg_mode = jnp.where(cmd == PDE_SLOW, BG_PDN_SLOW, bg_mode)
        bg_mode = jnp.where(cmd == SRE, BG_SR, bg_mode)
        bg_mode = jnp.where((cmd == PDX) | (cmd == SRX), BG_ACTIVE, bg_mode)
        new = _ScanState(
            bank_open=bank_open,
            bg_mode=bg_mode.astype(jnp.int32),
            prev_data=jnp.where(is_rw, data, s.prev_data),
            has_prev=s.has_prev | is_rw,
            prev_bank=jnp.where(is_rw, bank, s.prev_bank),
            last_col_in_bank=jnp.where(
                is_rw & bank_oh, col, s.last_col_in_bank),
            charge=s.charge + charge)
        return new, charge

    if carry is None:
        carry = empty_carry()
    init = _ScanState(
        bank_open=jnp.asarray(carry.open, jnp.bool_),
        bg_mode=jnp.asarray(carry.bg_mode, jnp.int32),
        prev_data=jnp.asarray(carry.prev_data, jnp.uint32),
        has_prev=jnp.asarray(carry.has_prev, jnp.bool_),
        prev_bank=jnp.asarray(carry.prev_bank, jnp.int32),
        last_col_in_bank=jnp.asarray(carry.last_col, jnp.int32),
        charge=jnp.asarray(0.0, dtype=jnp.float32))
    xs = (trace.cmd, trace.bank, trace.row, trace.col, trace.data, trace.dt)
    _, charges = jax.lax.scan(step, init, xs)
    return charges


@jax.jit
def trace_energy_scan(trace: CommandTrace, pp: PowerParams,
                      carry: SegmentCarry | None = None) -> EnergyReport:
    charges = trace_charges_scan(trace, pp, carry)
    return _report(jnp.sum(charges), trace.total_cycles())
