"""DRAM geometry, commands, timing, and command-trace representation.

Everything here models the exact device class characterized by the paper:
DDR3L-800 SO-DIMMs, one rank, 8 banks, 64-byte cache lines (512 bits),
nominal VDD = 1.35 V. Traces are JAX pytrees so the whole power pipeline
(ground-truth simulation, VAMPIRE, baselines) is jit/vmap-able.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Device constants (DDR3L-800, matching Table 1 of the paper)
# ---------------------------------------------------------------------------
VDD = 1.35                  # volts (DDR3L nominal)
N_BANKS = 8
LINE_BYTES = 64             # one cache line per RD/WR across the rank
LINE_BITS = LINE_BYTES * 8  # 512
LINE_WORDS = LINE_BYTES // 4  # 16 uint32 words
ROW_BITS = 15               # 32k rows per bank (2 GB single-rank module)
COLS_PER_ROW = 128          # 128 cache lines per 8 kB row
# Structural-variation surface geometry (paper Section 6 / Figs 19-22): rows
# are grouped into equal contiguous bands for the per-(bank, row-band)
# energy decomposition; band 0 (rows < 4096) is the reference band every
# standard loop and probe lives in.
N_ROW_BANDS = 8
ROW_BAND_SHIFT = ROW_BITS - 3   # row >> 12 -> band in [0, 8)
MT_PER_S = 800e6            # transfer rate used for all tests (FPGA limit)
CLOCK_HZ = MT_PER_S / 2     # 400 MHz DRAM clock
TCK_NS = 1e9 / CLOCK_HZ     # 2.5 ns


class Timing(NamedTuple):
    """DDR3L-800 timing parameters, in DRAM clock cycles (tCK = 2.5 ns)."""
    tRCD: int = 6    # 13.75 ns
    tRP: int = 6     # 13.75 ns
    tRAS: int = 14   # 35 ns
    tRC: int = 20    # tRAS + tRP
    tCCD: int = 4    # column-to-column (== burst length / 2 at DDR)
    tBURST: int = 4  # 8 beats DDR -> 4 clocks on the bus
    tRFC: int = 64   # 160 ns (2 Gb parts)
    tREFI: int = 3120  # 7.8 us
    tWR: int = 6     # 15 ns write recovery
    tRTP: int = 4    # read-to-precharge
    tCKE: int = 3    # power-down entry/exit
    tXP: int = 5     # exit from a (fast/active) power-down to a command
    tXPDLL: int = 24  # exit from slow power-down (DLL relock), 10 ns+
    tXS: int = 74    # exit from self-refresh to a command (tRFC + margin)
    # NOTE: new fields append at the END (positional Timing() constructions
    # and the analysis linter's rule table both rely on field order).
    tRRD: int = 4    # ACT-to-ACT, different banks (rolling)
    tFAW: int = 16   # four-activate window: at most 4 ACTs per tFAW
    tWTR: int = 4    # write-to-read turnaround (after the write burst)

TIMING = Timing()

# ---------------------------------------------------------------------------
# Commands
# ---------------------------------------------------------------------------
NOP = 0
ACT = 1
PRE = 2   # precharge one bank
RD = 3
WR = 4
REF = 5
PDE = 6   # fast power-down entry (DLL on); active power-down if banks open
PDX = 7   # power-down exit (fast, slow, and active power-down)
PREA = 8  # precharge all banks
PDE_SLOW = 9   # slow (precharge) power-down entry, DLL off
SRE = 10       # self-refresh entry (refresh becomes internal)
SRX = 11       # self-refresh exit

CMD_NAMES = {NOP: "NOP", ACT: "ACT", PRE: "PRE", RD: "RD", WR: "WR",
             REF: "REF", PDE: "PDE", PDX: "PDX", PREA: "PREA",
             PDE_SLOW: "PDE_SLOW", SRE: "SRE", SRX: "SRX"}

# Interleaving modes for the data-dependency model (paper Table 5).
IL_NONE = 0      # same bank & same column as previous RD/WR
IL_COL = 1       # same bank, different column
IL_BANK = 2      # different bank, same column as that bank's last access
IL_BANKCOL = 3   # different bank, different column
N_IL_MODES = 4
IL_NAMES = {IL_NONE: "none", IL_COL: "col", IL_BANK: "bank",
            IL_BANKCOL: "bank+col"}


class CommandTrace(NamedTuple):
    """A DRAM command trace as a structure of arrays.

    ``dt`` is the number of DRAM clock cycles from this command's issue slot
    to the next command's issue slot (i.e. the duration "owned" by this
    command); the trace's total duration is ``sum(dt)`` cycles. This is the
    same information content as DRAMPower-style timestamped traces but
    integrates trivially.
    """
    cmd: jax.Array    # (N,) int32, one of the command codes above
    bank: jax.Array   # (N,) int32 in [0, 8)
    row: jax.Array    # (N,) int32 in [0, 2^15)
    col: jax.Array    # (N,) int32 in [0, 128)
    data: jax.Array   # (N, 16) uint32 -- 64-byte line; zeros for non-RD/WR
    dt: jax.Array     # (N,) int32 cycles

    @property
    def n(self) -> int:
        return self.cmd.shape[0]

    def total_cycles(self):
        # int32 is plenty per trace chunk (<2^31 cycles ~ 5s of DRAM time);
        # long application traces are evaluated in chunks (see traces.py).
        return jnp.sum(self.dt, dtype=jnp.int32)

    def total_ns(self):
        return self.total_cycles() * TCK_NS


# commands that are illegal while in a power-down state (the clock-enable
# pin is low: no bank, data, or refresh activity may be issued; NOP, the
# exits, re-entry, and precharge at the tile seam stay legal)
_PDN_ILLEGAL = (ACT, RD, WR, REF, SRE)
# while in self-refresh ONLY NOP and the self-refresh exit are legal
_SR_LEGAL = (NOP, SRX)


def validate_low_power_transitions(cmds) -> None:
    """Raise ``ValueError`` on commands issued inside a low-power state
    that the device cannot accept (e.g. ``ACT`` during self-refresh).

    Walks the same background-state machine the integrator derives
    (``energy_model.structural_state``); called on every concrete
    ``make_trace`` so illegal traces fail at construction, before any
    energy is billed for them."""
    cmd = np.asarray(cmds)
    if not np.isin(cmd, (PDE, PDE_SLOW, SRE)).any():
        return  # no low-power entry -> nothing to check
    in_pdn = in_sr = False
    for i, c in enumerate(cmd.reshape(-1).tolist()):
        if in_sr and c not in _SR_LEGAL:
            raise ValueError(
                f"illegal command {CMD_NAMES.get(c, c)} at index {i}: "
                f"only NOP/SRX are legal during self-refresh")
        if in_pdn and c in _PDN_ILLEGAL:
            raise ValueError(
                f"illegal command {CMD_NAMES.get(c, c)} at index {i}: "
                f"not legal during power-down (exit with PDX first)")
        if c in (PDE, PDE_SLOW):
            in_pdn = True
        elif c == PDX:
            in_pdn = False
        elif c == SRE:
            in_sr = True
        elif c == SRX:
            in_sr = False


def make_trace(cmds, banks=None, rows=None, cols=None, data=None, dts=None,
               default_dt: int = 1) -> CommandTrace:
    """Build a CommandTrace from (possibly python-list) fields.

    Concrete (non-traced) command streams are checked against the
    low-power transition rules (:func:`validate_low_power_transitions`).
    The full protocol linter (``repro.analysis.trace_lint`` — every JEDEC
    timing rule, bank-state and background-state legality) additionally
    runs on every concrete construction when ``REPRO_TRACE_LINT`` is set
    to ``warn`` or ``strict``; it is off by default here because unit
    tests legitimately build toy traces with symbolic 1-cycle slots.  The
    repo's own generators (``idd_loops``, ``traces.app_trace``, encodings,
    the power-down policy) lint their outputs unconditionally."""
    try:
        validate_low_power_transitions(cmds)
    except ValueError:
        raise
    except Exception:
        pass  # traced/abstract inputs cannot be walked -- skip validation
    cmd = jnp.asarray(cmds, dtype=jnp.int32)
    n = cmd.shape[0]
    # default and broadcast fields are filled in host memory: a transfer,
    # not a device program per trace length
    z = np.zeros(n, dtype=np.int32)
    bank = jnp.asarray(z if banks is None else banks, dtype=jnp.int32)
    row = jnp.asarray(z if rows is None else rows, dtype=jnp.int32)
    col = jnp.asarray(z if cols is None else cols, dtype=jnp.int32)
    if data is None:
        data = np.zeros((n, LINE_WORDS), dtype=np.uint32)
    elif np.ndim(data) == 1:
        xp = jnp if isinstance(data, jax.Array) else np
        data = xp.broadcast_to(xp.asarray(data, dtype=xp.uint32)[None, :],
                               (n, LINE_WORDS))
    dat = jnp.asarray(data, dtype=jnp.uint32)
    dt = jnp.asarray(np.full(n, default_dt, dtype=np.int32) if dts is None
                     else dts, dtype=jnp.int32)
    trace = CommandTrace(cmd, bank, row, col, dat, dt)
    import os
    if os.environ.get("REPRO_TRACE_LINT", "off") != "off":
        from repro.analysis import trace_lint
        trace_lint.check_trace(trace, origin="make_trace",
                               mode=os.environ["REPRO_TRACE_LINT"])
    return trace


def concat_traces(*traces: CommandTrace) -> CommandTrace:
    """Concatenate concrete traces (in host memory, one transfer per
    field)."""
    return CommandTrace(*[jnp.asarray(np.concatenate([np.asarray(x)
                                                      for x in f]))
                          for f in zip(*traces)])


def tile_trace(trace: CommandTrace, reps: int) -> CommandTrace:
    """Repeat a concrete command loop ``reps`` times (paper's
    loop-until-measured), in host memory."""
    return CommandTrace(*[
        jnp.asarray(np.tile(np.asarray(x), (reps,) + (1,) * (x.ndim - 1)))
        for x in trace])


def pad_trace(trace: CommandTrace, length: int) -> CommandTrace:
    """NOP-pad a trace to ``length`` commands with ``dt == 0`` slots.

    A NOP that owns zero cycles draws zero charge and leaves every piece of
    integrator state (bank open/closed, power-down, previous-RD/WR data)
    untouched, so energy/current over the padded trace equals the original —
    this is what lets sweep points of unequal length share one compiled
    shape in the batched fleet engine.
    """
    n = trace.n
    assert length >= n, (length, n)
    pad = length - n
    if pad == 0:
        return trace
    zi = jnp.zeros(pad, dtype=jnp.int32)
    return CommandTrace(
        jnp.concatenate([trace.cmd, jnp.full(pad, NOP, dtype=jnp.int32)]),
        jnp.concatenate([trace.bank, zi]),
        jnp.concatenate([trace.row, zi]),
        jnp.concatenate([trace.col, zi]),
        jnp.concatenate([trace.data,
                         jnp.zeros((pad, LINE_WORDS), dtype=jnp.uint32)]),
        jnp.concatenate([trace.dt, zi]))


def stack_padded(traces, length: int, rows: int | None = None) -> CommandTrace:
    """Stack concrete traces into one ``(rows, length)`` batch, each
    NOP-padded like :func:`pad_trace`, with all-NOP rows after them up to
    ``rows`` (default: one row per trace).  The padding happens in host
    memory, so a batch of ragged lengths costs no per-length device
    program — one transfer per field."""
    fields = []
    for parts in zip(*traces):
        out = np.zeros((rows or len(parts), length) + parts[0].shape[1:],
                       np.asarray(parts[0]).dtype)     # zeros are NOPs
        for i, x in enumerate(parts):
            out[i, :x.shape[0]] = np.asarray(x)
        fields.append(jnp.asarray(out))
    return CommandTrace(*fields)


def batch_traces(traces_and_skips) -> tuple[CommandTrace, jax.Array]:
    """Stack variable-length traces into one fixed-shape batch.

    ``traces_and_skips`` is a sequence of ``(trace, skip)`` pairs; ``skip``
    generalizes the serial ``measure_current(skip=)`` handling: the first
    ``skip`` commands (one-time setup) are masked out of the average, as is
    all NOP/dt=0 padding. Returns ``(batch, weight)`` where every field of
    ``batch`` has a leading probe axis ``(P, N, ...)`` and ``weight`` is a
    float32 ``(P, N)`` mask of commands that count toward the measurement.
    """
    pairs = list(traces_and_skips)
    length = max(tr.n for tr, _ in pairs)
    batch = stack_padded([tr for tr, _ in pairs], length)
    idx = np.arange(length)
    weight = np.stack([(idx >= skip) & (idx < tr.n)
                       for tr, skip in pairs]).astype(np.float32)
    return batch, jnp.asarray(weight)


# ---------------------------------------------------------------------------
# Data-pattern helpers
# ---------------------------------------------------------------------------
def line_from_byte(byte_value: int) -> np.ndarray:
    """64-byte line where every byte equals ``byte_value`` (JEDEC style)."""
    b = byte_value & 0xFF
    w = b | (b << 8) | (b << 16) | (b << 24)
    return np.full(LINE_WORDS, w, dtype=np.uint32)


def line_with_n_ones(n_ones: int, rng: np.random.Generator | None = None) -> np.ndarray:
    """A 512-bit line with exactly ``n_ones`` ones (random positions)."""
    assert 0 <= n_ones <= LINE_BITS
    bits = np.zeros(LINE_BITS, dtype=np.uint8)
    if rng is None:
        bits[:n_ones] = 1  # deterministic: low bits first
    else:
        idx = rng.choice(LINE_BITS, size=n_ones, replace=False)
        bits[idx] = 1
    words = np.zeros(LINE_WORDS, dtype=np.uint32)
    for w in range(LINE_WORDS):
        chunk = bits[w * 32:(w + 1) * 32]
        words[w] = np.uint32(sum(int(b) << i for i, b in enumerate(chunk)))
    return words


def row_band(row):
    """Row-band index of a row address (int, numpy, or jax array)."""
    return row >> ROW_BAND_SHIFT


def popcount_u32(x: jax.Array) -> jax.Array:
    """Per-element population count of a uint32 array (pure jnp)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def line_ones(data: jax.Array) -> jax.Array:
    """Number of ones per 64-byte line. data: (..., 16) uint32 -> (...) int32."""
    return jnp.sum(popcount_u32(data), axis=-1)


def line_toggles(data: jax.Array, prev: jax.Array) -> jax.Array:
    """Number of bus wires that toggle between two consecutive lines."""
    return line_ones(jnp.bitwise_xor(data.astype(jnp.uint32),
                                     prev.astype(jnp.uint32)))
