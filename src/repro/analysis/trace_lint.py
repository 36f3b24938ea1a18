"""Declarative JEDEC-style DRAM protocol linter over command traces.

The paper's measurement methodology rests on precisely-timed command loops:
an IDD loop that violates tFAW, precharges inside tRAS, or drifts past the
tREFI deadline measures the wrong thing (PR 2 and PR 6 each found such bugs
only after they had corrupted energy numbers).  This module turns every
timing/state rule the generators must obey into a registered
:class:`TimingRule` evaluated in one of three interchangeable engines:

* :func:`lint_trace` — single trace, numpy, the construction-time hook the
  repo's generators call through :func:`check_generated`;
* :func:`lint_batch` / :func:`lint_traces` — the whole padded
  :class:`~repro.core.estimate_batch.TraceBatch` linted by jitted
  programs (vectorized cumulative-index/segment passes, no per-command
  Python), for serving ingestion and the CI corpus sweep.  Two programs
  share the rules: ``lint_count`` reduces every trace to its number of
  fired cells on the device, and only the traces that fired run again
  through ``lint_rules`` for their full diagnostics
  (:func:`lint_arrays_batched`).  The diagnostics are those of
  ``lint_rules`` over every trace; a clean batch fetches T counts, a
  batch where every trace fires pays both programs;
* :func:`reference_lint` — an independent per-command Python walk kept as
  the parity oracle (and the benchmark comparator).

All engines return structured :class:`Diagnostic` records (rule id, command
index, bank, severity, deficit in cycles) instead of a bare raise.

Rule semantics
--------------
Command *i* issues at ``t[i] = sum(dt[:i])``; ``dt`` is the cycles the slot
owns, so a dt=0 NOP is exactly invisible (the padding contract).  Every
rule sees only state from commands strictly before *i* ("last event time"
tables built by exclusive cumulative max — valid because ``t`` is
monotone; open/background-state questions use event *indices* so dt=0 ties
resolve by program order).  ``tREFI`` is a deadline on the *scheduler*, not
an interface timing, so it lints as a WARNING with one refresh-pair's worth
of slack (:data:`REFI_SLACK`); traces with no REF at all are vacuously
clean — JEDEC IDD loops measure with refresh suspended.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import warnings
from typing import Callable, Sequence

import numpy as np

from repro.core import dram
from repro.core.dram import (ACT, CMD_NAMES, NOP, N_BANKS, PDE, PDE_SLOW,
                             PDX, PRE, PREA, RD, REF, SRE, SRX, TIMING, WR,
                             CommandTrace, _PDN_ILLEGAL, _SR_LEGAL)
from repro.runtime.spans import span

NEG = -(1 << 30)          # "never happened" sentinel time/index
ERROR = "error"
WARNING = "warning"

# Slack on the tREFI deadline: the refresh pair's own slots (tRFC + tRP)
# plus one maximal request slot (the generators refresh after the RD/WR
# that crosses the deadline; app_trace's largest non-low-power slot is
# tBURST + 128 cycles of gap).
REFI_SLACK = TIMING.tRFC + TIMING.tRP + 160


# ---------------------------------------------------------------------------
# Diagnostics
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Diagnostic:
    """One rule violation at one command of one trace."""
    rule: str
    severity: str          # ERROR | WARNING
    trace_index: int
    cmd_index: int
    bank: int
    margin: int            # cycles short of the constraint (>0 = violated)
    message: str

    def __str__(self) -> str:  # pragma: no cover - formatting convenience
        return self.message


def _message(rule_id: str, cmd: int, i: int, b: int, margin: int) -> str:
    name = CMD_NAMES.get(int(cmd), str(int(cmd)))
    tail = f" (short by {margin} cycles)" if margin > 0 else ""
    return (f"{rule_id}: {name} at command #{i} bank {b} violates "
            f"{RULES[rule_id].description}{tail}")


# ---------------------------------------------------------------------------
# Rule registry
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TimingRule:
    """A declaratively registered protocol rule.

    ``check(ctx) -> (mask, deficit, bank)``: per-command violation mask,
    cycles-short deficit, and the bank each violation charges against —
    computed with backend-agnostic array code (the same formula runs under
    numpy and under jit/vmap).
    """
    rule_id: str
    severity: str
    description: str
    check: Callable


RULES: dict[str, TimingRule] = {}


def rule(rule_id: str, description: str, severity: str = ERROR):
    """Decorator registering a rule's check function."""
    def deco(fn):
        RULES[rule_id] = TimingRule(rule_id, severity, description, fn)
        return fn
    return deco


# ---------------------------------------------------------------------------
# Backend adapters (the only three primitives numpy and jax spell apart)
# ---------------------------------------------------------------------------
class _NumpyBackend:
    name = "numpy"

    @staticmethod
    def xp():
        return np

    @staticmethod
    def exclusive_cummax(x, seed=NEG):
        c = np.maximum.accumulate(x, axis=0)
        out = np.empty_like(c)
        out[:1] = NEG
        out[1:] = c[:-1]
        return np.maximum(out, seed)

    @staticmethod
    def scatter_times(size: int, slot, times):
        """``arr = full(size, NEG); arr[slot] = times`` with slot
        ``size - 1`` reserved as a guaranteed-NEG dump index."""
        arr = np.full(size, NEG, dtype=np.asarray(times).dtype)
        arr[slot] = times
        arr[size - 1] = NEG
        return arr


class _JaxBackend:
    name = "jax"

    @staticmethod
    def xp():
        import jax.numpy as jnp
        return jnp

    @staticmethod
    def exclusive_cummax(x, seed=NEG):
        import jax
        import jax.numpy as jnp
        c = jax.lax.cummax(x, axis=0)
        return jnp.maximum(jnp.concatenate(
            [jnp.full_like(c[:1], NEG), c[:-1]], axis=0), seed)

    @staticmethod
    def scatter_times(size: int, slot, times):
        import jax.numpy as jnp
        arr = jnp.full(size, NEG, dtype=times.dtype)
        return arr.at[slot].set(times).at[size - 1].set(NEG)


# ---------------------------------------------------------------------------
# Segment carry: what a segment of a longer trace inherits from before it.
# One int32 vector per segment, read by _Ctx as the seed of every "last
# event" table.  Times are the segment's own, 0 at its first command, so
# no table ever holds whole-trace time: an event 2^30 or more cycles
# before the segment is clamped to NEG, which no interface timing can
# reach.  empty_carry() (nothing before) is the identity.
# ---------------------------------------------------------------------------
C_START = 0                        # the trace's first command (<= 0)
C_ACT_B = slice(1, 9)              # per bank: last ACT time
C_CLOSE_B = slice(9, 17)           # last PRE/PREA time
C_WR_B = slice(17, 25)             # last WR time
C_RD_B = slice(25, 33)             # last RD time
C_OPEN_B = slice(33, 41)           # bank open (0/1)
C_REF, C_PDX, C_SRX, C_PDX_SLOW = 41, 42, 43, 44   # last times
C_IN_PDN, C_IN_SR, C_SLOW_ENTRY = 45, 46, 47        # flags (0/1)
C_ACT4 = slice(48, 52)             # the last four ACT times, oldest first
CARRY_WIDTH = 52


def empty_carry() -> np.ndarray:
    """The carry of a trace's first segment (and of a whole trace)."""
    c = np.full(CARRY_WIDTH, NEG, np.int32)
    c[C_START] = 0
    c[C_OPEN_B] = 0
    c[[C_IN_PDN, C_IN_SR, C_SLOW_ENTRY]] = 0
    return c


def lint_carry(dt, starts, ev) -> np.ndarray:
    """The (k, CARRY_WIDTH) int32 carries of the segments of one trace
    that begin at ``starts``, from the trace's cycles ``dt`` and its
    ``energy_model.events_before`` at those starts ``ev`` (host numpy).
    Times are in cycles from each segment's first command, clamped at
    NEG."""
    dt = np.asarray(dt, np.int64)
    t = np.cumsum(dt) - dt
    t0 = t[np.asarray(starts, np.int64)]

    def time_of(i):
        i = np.asarray(i)
        rel = t[np.maximum(i, 0)] - t0.reshape((-1,) + (1,) * (i.ndim - 1))
        return np.where(i >= 0, np.maximum(rel, NEG), NEG)

    out = np.tile(empty_carry(), (len(t0), 1))
    out[:, C_START] = np.maximum(-t0, NEG)
    out[:, C_ACT_B] = time_of(ev.act)
    out[:, C_CLOSE_B] = time_of(ev.close)
    out[:, C_WR_B] = time_of(ev.wr)
    out[:, C_RD_B] = time_of(ev.rd)
    out[:, C_OPEN_B] = ev.act > ev.close
    out[:, C_REF] = time_of(ev.ref)
    out[:, C_PDX] = time_of(ev.pdx)
    out[:, C_SRX] = time_of(ev.srx)
    out[:, C_PDX_SLOW] = time_of(ev.pdx_slow)
    out[:, C_IN_PDN] = np.maximum(ev.pde, ev.pde_slow) > ev.pdx
    out[:, C_IN_SR] = ev.sre > ev.srx
    out[:, C_SLOW_ENTRY] = ev.pde_slow > ev.pde
    out[:, C_ACT4] = time_of(ev.act4)
    return out


# ---------------------------------------------------------------------------
# Context: every derived table the rules read, built in one vectorized pass
# ---------------------------------------------------------------------------
class _Ctx:
    """Per-trace rule-evaluation context (plain attribute bag).  ``carry``
    (a CARRY_WIDTH vector, None for a whole trace) seeds every table with
    the segment's past, in the segment's own time."""

    def __init__(self, cmd, bank, dt, carry, backend):
        xp = backend.xp()
        self.xp = xp
        self.T = TIMING
        if carry is None:
            carry = empty_carry()
        n = cmd.shape[0]
        self.n = n
        self.cmd = cmd
        self.bank = bank
        self.dt = dt
        idx = xp.arange(n)
        self.t = xp.cumsum(dt, axis=0) - dt           # issue time of slot i
        self.t_start = carry[C_START]                 # the trace's start

        self.is_act = cmd == ACT
        self.is_pre = cmd == PRE
        self.is_prea = cmd == PREA
        self.is_rd = cmd == RD
        self.is_wr = cmd == WR
        self.is_rw = self.is_rd | self.is_wr
        self.is_ref = cmd == REF
        self.nonnop = cmd != NOP

        onehot = bank[:, None] == xp.arange(N_BANKS)[None, :]
        act_b = self.is_act[:, None] & onehot
        close_b = (self.is_pre[:, None] & onehot) | self.is_prea[:, None]
        wr_b = self.is_wr[:, None] & onehot
        rd_b = self.is_rd[:, None] & onehot
        self.close_b = close_b

        def last_t(ev, seed=NEG):
            return backend.exclusive_cummax(xp.where(ev, self.t, NEG), seed)

        def last_t_b(ev_b, seed):
            return backend.exclusive_cummax(
                xp.where(ev_b, self.t[:, None], NEG), seed)

        # indices: a carried event sits at -1, before the segment's own
        def last_i(ev, carried=False):
            return backend.exclusive_cummax(xp.where(ev, idx, NEG),
                                            xp.where(carried, -1, NEG))

        def last_i_b(ev_b, carried=False):
            return backend.exclusive_cummax(xp.where(ev_b, idx[:, None], NEG),
                                            xp.where(carried, -1, NEG))

        def own(tbl):
            return xp.take_along_axis(tbl, bank[:, None], axis=1)[:, 0]

        # per-bank last-event time tables (strictly before i) + own gathers
        self.t_act_b = last_t_b(act_b, carry[C_ACT_B])
        self.t_wr_b = last_t_b(wr_b, carry[C_WR_B])
        self.t_rd_b = last_t_b(rd_b, carry[C_RD_B])
        self.t_act_own = own(self.t_act_b)
        self.t_close_own = own(last_t_b(close_b, carry[C_CLOSE_B]))

        # bank open state before i: index-based so dt=0 ties keep order
        self.open_b = (last_i_b(act_b, carry[C_OPEN_B] > 0)
                       > last_i_b(close_b))
        self.open_own = own(self.open_b)

        # any-bank scalars (the carried ones are the latest of a bank's)
        self.t_act_any = last_t(self.is_act, carry[C_ACT_B].max())
        self.t_wr_any = last_t(self.is_wr, carry[C_WR_B].max())
        self.t_rw_any = last_t(self.is_rw, xp.maximum(carry[C_WR_B].max(),
                                                      carry[C_RD_B].max()))
        self.t_ref = last_t(self.is_ref, carry[C_REF])

        # background-state machine (power-down / self-refresh)
        is_pde = cmd == PDE
        is_pds = cmd == PDE_SLOW
        is_pdx = cmd == PDX
        is_sre = cmd == SRE
        is_srx = cmd == SRX
        self.in_pdn = (last_i(is_pde | is_pds, carry[C_IN_PDN] > 0)
                       > last_i(is_pdx))
        self.in_sr = last_i(is_sre, carry[C_IN_SR] > 0) > last_i(is_srx)
        self.t_pdx = last_t(is_pdx, carry[C_PDX])
        self.t_srx = last_t(is_srx, carry[C_SRX])
        # a PDX exiting a SLOW power-down needs the DLL relock (tXPDLL)
        slow_entry = (last_i(is_pds, carry[C_SLOW_ENTRY] > 0)
                      > last_i(is_pde))
        self.t_pdx_slow = last_t(is_pdx & slow_entry, carry[C_PDX_SLOW])

        # tFAW: time of the 4th-previous ACT (rolling four-activate
        # window), the carried four ACTs first
        k = xp.cumsum(self.is_act.astype(self.t.dtype), axis=0)
        slot = xp.where(self.is_act, k - 1, n)
        act_times = xp.concatenate([
            carry[C_ACT4].astype(self.t.dtype),
            backend.scatter_times(n + 1, slot, self.t)])
        self.t_act_4ago = act_times[xp.where(self.is_act, k - 1, n + 4)]


# ---------------------------------------------------------------------------
# The rules (check(ctx) -> (mask, deficit, bank))
# ---------------------------------------------------------------------------
def _scalar(ctx, base, req):
    """Helper for rules on the command's own bank: violated when the base
    condition holds and the command issues before ``req``."""
    deficit = req - ctx.t
    return base & (deficit > 0), deficit, ctx.bank


def _per_bank(ctx, viol_b, deficit_b):
    """Helper for close-side rules that can violate on any bank at once:
    report the worst-deficit bank (first such bank on ties)."""
    deficit_b = ctx.xp.where(viol_b, deficit_b, 0)
    return (viol_b.any(axis=1), deficit_b.max(axis=1),
            deficit_b.argmax(axis=1).astype(ctx.bank.dtype))


@rule("tRCD", "RD/WR before the bank's activate completed (tRCD)")
def _r_trcd(c):
    mask, deficit, bank = _scalar(c, c.is_rw, c.t_act_own + c.T.tRCD)
    return mask & c.open_own, deficit, bank


@rule("tRP", "ACT before the bank's precharge completed (tRP)")
def _r_trp(c):
    return _scalar(c, c.is_act, c.t_close_own + c.T.tRP)


@rule("tRAS", "precharge before the bank's row was open tRAS cycles")
def _r_tras(c):
    req = c.t_act_b + c.T.tRAS
    viol = c.close_b & c.open_b & (c.t[:, None] < req)
    return _per_bank(c, viol, req - c.t[:, None])


@rule("tRC", "ACT-to-ACT on one bank inside tRC")
def _r_trc(c):
    return _scalar(c, c.is_act, c.t_act_own + c.T.tRC)


@rule("tRRD", "ACT-to-ACT across banks inside tRRD")
def _r_trrd(c):
    return _scalar(c, c.is_act, c.t_act_any + c.T.tRRD)


@rule("tFAW", "fifth ACT inside the rolling four-activate window (tFAW)")
def _r_tfaw(c):
    return _scalar(c, c.is_act, c.t_act_4ago + c.T.tFAW)


@rule("tWR", "precharge inside the write-recovery window (tWR)")
def _r_twr(c):
    req = c.t_wr_b + c.T.tBURST + c.T.tWR
    viol = c.close_b & c.open_b & (c.t[:, None] < req)
    return _per_bank(c, viol, req - c.t[:, None])


@rule("tRTP", "precharge inside the read-to-precharge window (tRTP)")
def _r_trtp(c):
    req = c.t_rd_b + c.T.tRTP
    viol = c.close_b & c.open_b & (c.t[:, None] < req)
    return _per_bank(c, viol, req - c.t[:, None])


@rule("tWTR", "read inside the write-to-read turnaround (tWTR)")
def _r_twtr(c):
    return _scalar(c, c.is_rd, c.t_wr_any + c.T.tBURST + c.T.tWTR)


@rule("tCCD", "column command inside the column-to-column window (tCCD)")
def _r_tccd(c):
    return _scalar(c, c.is_rw, c.t_rw_any + c.T.tCCD)


@rule("tRFC", "command issued while a refresh was still in flight (tRFC)")
def _r_trfc(c):
    return _scalar(c, c.nonnop, c.t_ref + c.T.tRFC)


@rule("tXP", "command issued inside the power-down exit latency (tXP)")
def _r_txp(c):
    return _scalar(c, c.nonnop, c.t_pdx + c.T.tXP)


@rule("tXPDLL", "RD/WR before the DLL relocked after a slow power-down "
                "exit (tXPDLL)")
def _r_txpdll(c):
    return _scalar(c, c.is_rw, c.t_pdx_slow + c.T.tXPDLL)


@rule("tXS", "command issued inside the self-refresh exit latency (tXS)")
def _r_txs(c):
    return _scalar(c, c.nonnop, c.t_srx + c.T.tXS)


@rule("BANK_RW_CLOSED", "RD/WR to a bank with no open row")
def _r_rw_closed(c):
    mask = c.is_rw & ~c.open_own
    return mask, c.xp.where(mask, 1, 0), c.bank


@rule("BANK_ACT_OPEN", "ACT to a bank that already has an open row")
def _r_act_open(c):
    mask = c.is_act & c.open_own
    return mask, c.xp.where(mask, 1, 0), c.bank


@rule("REF_BANK_OPEN", "REF issued with banks still open")
def _r_ref_open(c):
    viol = c.is_ref[:, None] & c.open_b
    return _per_bank(c, viol, c.xp.where(viol, 1, 0))


@rule("PDN_ILLEGAL_CMD", "command not legal during power-down")
def _r_pdn(c):
    illegal = c.cmd == _PDN_ILLEGAL[0]
    for code in _PDN_ILLEGAL[1:]:
        illegal = illegal | (c.cmd == code)
    mask = c.in_pdn & illegal
    return mask, c.xp.where(mask, 1, 0), c.bank


@rule("SR_ILLEGAL_CMD", "command not legal during self-refresh")
def _r_sr(c):
    legal = c.cmd == _SR_LEGAL[0]
    for code in _SR_LEGAL[1:]:
        legal = legal | (c.cmd == code)
    mask = c.in_sr & ~legal
    return mask, c.xp.where(mask, 1, 0), c.bank


@rule("DT_NEGATIVE", "command slot owns a negative number of cycles")
def _r_dt(c):
    mask = c.dt < 0
    return mask, c.xp.where(mask, -c.dt, 0), c.bank


@rule("tREFI", "refresh arrived past the tREFI deadline (plus scheduling "
               "slack)", severity=WARNING)
def _r_trefi(c):
    anchor = c.xp.maximum(c.xp.maximum(c.t_ref, c.t_srx), c.t_start)
    deadline = anchor + c.T.tREFI + REFI_SLACK
    deficit = c.t - deadline
    return c.is_ref & (deficit > 0), deficit, c.bank


_RULE_ORDER: tuple[str, ...] = tuple(RULES)


# ---------------------------------------------------------------------------
# Engines
# ---------------------------------------------------------------------------
def _eval_rules(cmd, bank, dt, carry, backend):
    """(R, n) stacked (mask, deficit, bank) over every registered rule."""
    ctx = _Ctx(cmd, bank, dt, carry, backend)
    xp = ctx.xp
    masks, deficits, banks = [], [], []
    for rid in _RULE_ORDER:
        m, d, b = RULES[rid].check(ctx)
        masks.append(m)
        deficits.append(xp.where(m, d, 0))
        banks.append(b)
    return xp.stack(masks), xp.stack(deficits), xp.stack(banks)


def _extract(mask, deficit, bank, cmd, trace_index: int,
             base: int = 0) -> list[Diagnostic]:
    """Diagnostics of one row's stacks; ``base`` is the row's first
    command's index in its whole trace."""
    out = []
    rule_rows, cmd_idx = np.nonzero(mask)
    for r, j in zip(rule_rows.tolist(), cmd_idx.tolist()):
        i = base + j
        rid = _RULE_ORDER[r]
        margin = int(deficit[r, j])
        b = int(bank[r, j])
        out.append(Diagnostic(rid, RULES[rid].severity, trace_index, i, b,
                              margin, _message(rid, int(cmd[j]), i, b,
                                               margin)))
    out.sort(key=lambda d: (d.trace_index, d.cmd_index,
                            _RULE_ORDER.index(d.rule)))
    return out


def lint_trace(trace: CommandTrace, trace_index: int = 0) -> list[Diagnostic]:
    """Lint one trace with the numpy engine (the construction-time hook)."""
    cmd = np.asarray(trace.cmd, dtype=np.int64)
    bank = np.asarray(trace.bank, dtype=np.int64)
    dt = np.asarray(trace.dt, dtype=np.int64)
    mask, deficit, bank_r = _eval_rules(cmd, bank, dt, None, _NumpyBackend)
    return _extract(mask, deficit, bank_r, cmd, trace_index)


@functools.cache
def _empty_plane(rows: int):
    """The (rows, CARRY_WIDTH) carries of a batch of whole traces, kept on
    the device: sent once, not with every batch."""
    import jax
    return jax.device_put(np.tile(empty_carry(), (rows, 1)))


@functools.cache
def _programs():
    """``(lint_count, lint_rules)``, the two jitted (T, N) batch programs,
    built lazily (keeps numpy-only callers of :func:`lint_trace` free of
    any jax dispatch).  Both read the rows' (T, CARRY_WIDTH) carries.
    ``lint_count`` gives each row's number of fired (rule, command)
    cells, (T,) int32: XLA fuses the masks into the sum and drops the
    deficit and bank stacks.  ``lint_rules`` gives the three (T, R, N)
    stacks (mask, deficit, bank)."""
    import jax

    def stacks(cmd, bank, dt, carry):
        return jax.vmap(
            lambda c, b, d, k: _eval_rules(c, b, d, k, _JaxBackend))(
                cmd, bank, dt, carry)

    @jax.jit
    def lint_count(cmd, bank, dt, carry):
        return stacks(cmd, bank, dt, carry)[0].sum(axis=(1, 2),
                                                   dtype="int32")

    @jax.jit
    def lint_rules(cmd, bank, dt, carry):
        return stacks(cmd, bank, dt, carry)

    return lint_count, lint_rules


def lint_arrays_batched(cmd, bank, dt, carry=None, owner=None,
                        base=None) -> list[Diagnostic]:
    """Lint a padded (T, N) command batch: count on the device, fetch what
    fired.

    ``carry`` is the rows' (T, CARRY_WIDTH) segment carries (None: every
    row a whole trace); a diagnostic of row ``r`` names trace
    ``owner[r]`` and command ``base[r]`` + its index in the row (default:
    row ``r``, from 0), so a trace cut into rows gets its diagnostics in
    whole-trace indices.

    ``lint_count`` runs over every row and only its (T,) counts come to
    the host.  The rows that fired, and only they, run again through
    ``lint_rules``, whose (k, R, N) stacks are extracted; k is padded to a
    power of two (at most T) with NOP/dt=0 rows, inert under every rule,
    so compiled shapes stay bounded.  The diagnostics are exactly those
    of ``lint_rules`` over every row.  A batch where every trace fires
    (the tests' seeded violations) pays ``lint_count`` and a
    second trip of its planes on top of ``lint_rules`` over every row;
    served traffic, which never fires, pays ``lint_count`` alone.

    Spans: ``lint.rules`` (the host planes to the device and
    ``lint_count``, waited for; ``bytes`` sent), ``lint.fetch`` (the
    counts down, and the fired traces' planes up and stacks down;
    ``bytes`` moved, ``fired_traces`` re-run) and ``lint.extract`` (the
    diagnostics), each recorded on every call."""
    import jax
    lint_count, lint_rules = _programs()
    rows = np.shape(cmd)[0]
    if carry is None:
        carry = np.tile(empty_carry(), (rows, 1))
    owner = np.arange(rows) if owner is None else owner
    base = np.zeros(rows, np.int64) if base is None else base
    with span("lint.rules") as s:
        s.attrs["bytes"] = sum(x.nbytes for x in (cmd, bank, dt, carry)
                               if isinstance(x, np.ndarray))
        counts = jax.block_until_ready(lint_count(cmd, bank, dt, carry))
    with span("lint.fetch") as s:
        counts = np.asarray(counts)
        fired = np.flatnonzero(counts)
        s.attrs["bytes"] = counts.nbytes
        s.attrs["fired_traces"] = len(fired)
        if len(fired):
            pad = min(1 << (len(fired) - 1).bit_length(), len(counts)) \
                - len(fired)
            planes = [np.pad(np.asarray(x)[fired], ((0, pad), (0, 0)))
                      for x in (cmd, bank, dt, carry)]
            found = [np.asarray(x) for x in lint_rules(*planes)]
            s.attrs["bytes"] += sum(x.nbytes for x in planes + found)
    with span("lint.extract"):
        out = []
        for k, r in enumerate(fired.tolist()):
            mask, deficit, bank_r = (x[k] for x in found)
            out.extend(_extract(mask, deficit, bank_r, planes[0][k],
                                int(owner[r]), int(base[r])))
    return out


def lint_batch(tb) -> list[Diagnostic]:
    """Lint a prebuilt :class:`~repro.core.estimate_batch.TraceBatch`
    through :func:`lint_arrays_batched`.  NOP/dt=0 padding is inert under every rule, so no
    weight masking is needed — pad rows simply cannot violate anything."""
    return lint_arrays_batched(tb.trace.cmd, tb.trace.bank, tb.trace.dt)


def lint_traces(traces: Sequence) -> list[Diagnostic]:
    """Lint a sequence of ragged traces through the batched engine
    (:func:`lint_arrays_batched`: count on the device, full diagnostics
    for the traces that fired, the same list as one full pass), padding
    the rows and their length to the next power of two so repeated calls
    share compiled shapes.

    Each item is a whole trace (a ``CommandTrace``: one row, nothing
    before it) or a trace cut into rows (``serving.ring.Segments``: its
    ``trace``, ascending row ``bounds`` from 0 to its length, and each
    row's ``lint_carry`` of the commands before it; one row lints as a
    whole trace).  A cut trace's
    diagnostics are the whole trace's, in whole-trace command indices,
    and no row needs a length past its own.  Each row is timed from its
    first command, so a row must span fewer than 2^30 cycles; a cut
    trace may span any number.

    Only the three fields the rules read are padded (host-side, one
    allocation each): the NOP/dt=0 pad rows are inert under every rule, so
    no per-trace :func:`~repro.core.dram.pad_trace` round-trip (which
    would also copy row/col/data) is needed."""
    if not traces:
        return []
    with span("lint.pack"):
        rows, carries = [], []
        for i, item in enumerate(traces):
            if isinstance(item, CommandTrace) or len(item.bounds) == 2:
                tr = item if isinstance(item, CommandTrace) else item.trace
                item_rows = [(tr, 0, int(tr.n))]
                carries.append(None)
            else:
                b = [int(x) for x in item.bounds]
                item_rows = [(item.trace, lo, hi)
                             for lo, hi in zip(b[:-1], b[1:])]
                carries.append(item.lint_carry)
            rows += [(i, tr, lo, hi) for tr, lo, hi in item_rows]
        count = 1 << (len(rows) - 1).bit_length()
        longest = max(hi - lo for _, _, lo, hi in rows)
        length = 1 << max(longest - 1, 1).bit_length()
        cmd = np.zeros((count, length), np.int32)   # NOP == 0
        bank = np.zeros((count, length), np.int32)
        dt = np.zeros((count, length), np.int32)
        for r, (_, tr, lo, hi) in enumerate(rows):
            cmd[r, :hi - lo] = np.asarray(tr.cmd)[lo:hi]
            bank[r, :hi - lo] = np.asarray(tr.bank)[lo:hi]
            dt[r, :hi - lo] = np.asarray(tr.dt)[lo:hi]
        if any(c is not None for c in carries):
            carry = np.tile(empty_carry(), (count, 1))
            carry[:len(rows)] = np.concatenate(
                [empty_carry()[None] if c is None else c for c in carries])
        else:
            carry = _empty_plane(count)
        owner = np.zeros(count, np.int64)
        owner[:len(rows)] = [i for i, _, _, _ in rows]
        base = np.zeros(count, np.int64)
        base[:len(rows)] = [lo for _, _, lo, _ in rows]
    return lint_arrays_batched(cmd, bank, dt, carry, owner, base)


# ---------------------------------------------------------------------------
# Reference engine: an independent per-command Python walk (parity oracle)
# ---------------------------------------------------------------------------
def reference_lint(trace: CommandTrace,
                   trace_index: int = 0) -> list[Diagnostic]:
    """Per-command reference checker, deliberately implemented as a plain
    state-machine walk sharing nothing with the vectorized engine beyond
    the rule table — the parity tests pin the two against each other."""
    T = TIMING
    cmd = np.asarray(trace.cmd).tolist()
    bank = np.asarray(trace.bank).tolist()
    dts = np.asarray(trace.dt).tolist()
    out: list[Diagnostic] = []

    act_t = [NEG] * N_BANKS
    close_t = [NEG] * N_BANKS
    wr_t = [NEG] * N_BANKS
    rd_t = [NEG] * N_BANKS
    open_b = [False] * N_BANKS
    act_times: list[int] = []
    last_act = last_wr = last_rw = NEG
    last_ref = last_pdx = last_pdx_slow = last_srx = NEG
    in_pdn = in_sr = False
    slow_entry = False
    t = 0

    def add(rid, i, b, margin):
        out.append(Diagnostic(rid, RULES[rid].severity, trace_index, i,
                              int(b), int(margin),
                              _message(rid, cmd[i], i, int(b), int(margin))))

    def worst_open(i, targets, ref_t, lead, rid):
        deficit, at = 0, -1
        for b in targets:
            if open_b[b] and t < ref_t[b] + lead:
                d = ref_t[b] + lead - t
                if d > deficit:
                    deficit, at = d, b
        if at >= 0:
            add(rid, i, at, deficit)

    for i in range(len(cmd)):
        c, b, d = cmd[i], bank[i], dts[i]
        if d < 0:
            add("DT_NEGATIVE", i, b, -d)
        if c != NOP:
            if t < last_ref + T.tRFC:
                add("tRFC", i, b, last_ref + T.tRFC - t)
            if t < last_pdx + T.tXP:
                add("tXP", i, b, last_pdx + T.tXP - t)
            if t < last_srx + T.tXS:
                add("tXS", i, b, last_srx + T.tXS - t)
        if in_pdn and c in _PDN_ILLEGAL:
            add("PDN_ILLEGAL_CMD", i, b, 1)
        if in_sr and c not in _SR_LEGAL:
            add("SR_ILLEGAL_CMD", i, b, 1)

        if c == ACT:
            if open_b[b]:
                add("BANK_ACT_OPEN", i, b, 1)
            if t < close_t[b] + T.tRP:
                add("tRP", i, b, close_t[b] + T.tRP - t)
            if t < act_t[b] + T.tRC:
                add("tRC", i, b, act_t[b] + T.tRC - t)
            if t < last_act + T.tRRD:
                add("tRRD", i, b, last_act + T.tRRD - t)
            if len(act_times) >= 4 and t < act_times[-4] + T.tFAW:
                add("tFAW", i, b, act_times[-4] + T.tFAW - t)
            act_t[b] = t
            open_b[b] = True
            last_act = t
            act_times.append(t)
        elif c in (RD, WR):
            if not open_b[b]:
                add("BANK_RW_CLOSED", i, b, 1)
            elif t < act_t[b] + T.tRCD:
                add("tRCD", i, b, act_t[b] + T.tRCD - t)
            if t < last_rw + T.tCCD:
                add("tCCD", i, b, last_rw + T.tCCD - t)
            if t < last_pdx_slow + T.tXPDLL:
                add("tXPDLL", i, b, last_pdx_slow + T.tXPDLL - t)
            if c == RD:
                if t < last_wr + T.tBURST + T.tWTR:
                    add("tWTR", i, b, last_wr + T.tBURST + T.tWTR - t)
                rd_t[b] = t
            else:
                wr_t[b] = t
                last_wr = t
            last_rw = t
        elif c in (PRE, PREA):
            targets = range(N_BANKS) if c == PREA else (b,)
            worst_open(i, targets, act_t, T.tRAS, "tRAS")
            worst_open(i, targets, wr_t, T.tBURST + T.tWR, "tWR")
            worst_open(i, targets, rd_t, T.tRTP, "tRTP")
            for tb in targets:
                close_t[tb] = t
                open_b[tb] = False
        elif c == REF:
            for ob in range(N_BANKS):
                if open_b[ob]:
                    add("REF_BANK_OPEN", i, ob, 1)
                    break
            anchor = max(last_ref, last_srx, 0)
            if t > anchor + T.tREFI + REFI_SLACK:
                add("tREFI", i, b, t - (anchor + T.tREFI + REFI_SLACK))
            last_ref = t
        elif c == PDE:
            in_pdn = True
            slow_entry = False
        elif c == PDE_SLOW:
            in_pdn = True
            slow_entry = True
        elif c == PDX:
            last_pdx = t
            if slow_entry:
                last_pdx_slow = t
            in_pdn = False
        elif c == SRE:
            in_sr = True
        elif c == SRX:
            in_sr = False
            last_srx = t
        t += d
    out.sort(key=lambda di: (di.trace_index, di.cmd_index,
                             _RULE_ORDER.index(di.rule)))
    return out


# ---------------------------------------------------------------------------
# Policy surface: how producers/consumers consume the diagnostics
# ---------------------------------------------------------------------------
class TraceProtocolError(ValueError):
    """A trace violated ERROR-severity protocol rules.  Carries the
    structured diagnostics so callers (serving ingestion, tests) can match
    on rule id / command index instead of parsing the message."""

    def __init__(self, diagnostics: Sequence[Diagnostic], origin: str = ""):
        self.diagnostics = tuple(diagnostics)
        self.origin = origin
        shown = [d.message for d in self.diagnostics[:8]]
        if len(self.diagnostics) > len(shown):
            shown.append(f"... {len(self.diagnostics) - len(shown)} more")
        super().__init__(
            f"protocol-illegal trace from {origin or 'caller'}: "
            f"{len(self.diagnostics)} violation(s)\n  " + "\n  ".join(shown))


def errors_of(diags: Sequence[Diagnostic]) -> list[Diagnostic]:
    return [d for d in diags if d.severity == ERROR]


def _is_traced(trace: CommandTrace) -> bool:
    try:
        import jax
        tracer = jax.core.Tracer
    except Exception:  # pragma: no cover - exotic jax layouts
        return True    # fail safe: cannot tell, skip linting
    return isinstance(trace.cmd, tracer)


def check_generated(trace: CommandTrace, origin: str) -> CommandTrace:
    """The strict construction-time guard every repo generator calls on its
    output: raises :class:`TraceProtocolError` on ERROR diagnostics, warns
    on WARNING ones, and passes the trace through.  Traced/abstract inputs
    are skipped (shape-polymorphic callers cannot be walked).  Set
    ``REPRO_TRACE_LINT=off`` to disable (e.g. when intentionally producing
    broken traces to study)."""
    if os.environ.get("REPRO_TRACE_LINT", "").lower() == "off":
        return trace
    if _is_traced(trace):
        return trace
    diags = lint_trace(trace)
    errors = errors_of(diags)
    if errors:
        raise TraceProtocolError(errors, origin)
    for d in diags:
        warnings.warn(f"[{origin}] {d.message}", stacklevel=3)
    return trace


def check_trace(trace: CommandTrace, origin: str = "make_trace",
                mode: str = "strict") -> list[Diagnostic]:
    """The opt-in ``dram.make_trace`` hook (``REPRO_TRACE_LINT=warn|strict``):
    lint any concrete construction, warn or raise per ``mode``."""
    if _is_traced(trace):
        return []
    diags = lint_trace(trace)
    if mode == "strict":
        errors = errors_of(diags)
        if errors:
            raise TraceProtocolError(errors, origin)
    for d in diags:
        warnings.warn(f"[{origin}] {d.message}", stacklevel=3)
    return diags


def lint_ingested(traces: Sequence[CommandTrace],
                  origin: str = "ingestion") -> None:
    """Strict batched gate for externally ingested traces (the serving
    ``--power-report`` path): one batched lint over the whole sequence
    (:func:`lint_traces`), raising with rule id + command index on any ERROR."""
    errors = errors_of(lint_traces(traces))
    if errors:
        raise TraceProtocolError(errors, origin)
