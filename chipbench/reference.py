"""Plain reference of the VAMPIRE per-command energy integrator.

It imports nothing of the program.  A trace is walked one command at a
time, as the paper's model states it (arXiv:1807.05102 §9): each command
owns ``dt`` cycles of the background current of the module's state (bank
open/closed, fast/slow/active power-down, self-refresh), a RD/WR adds the
data-dependent current of paper Eq. 2 over its burst, an ACT its
activate+precharge charge scaled by the row-address ones and the
structural (bank, row-band) factor, a REF its refresh charge.  The walk
gives the per-command state; the currents are then evaluated in numpy,
in float64 by default.

``dtype=bfloat16`` computes every per-command current and charge in
bfloat16 (accumulating in float32): the benchmark's control, which the
comparison has to refuse.

Parameters are a dict of numpy arrays with a leading axis of parameter
sets (vendors, or modules), keyed like the fields of the program's
``PowerParams``.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

TCK_NS = 2.5            # DDR3L-800: 400 MHz clock
VDD = 1.35
T_BURST = 4
LINE_BITS = 512
N_BANKS = 8
ROW_BAND_SHIFT = 12     # 32k rows per bank in 8 bands
N_CELLS = 64            # (bank, row band) cells of the structural surface
NOP, ACT, PRE, RD, WR, REF, PDE, PDX, PREA, PDE_SLOW, SRE, SRX = range(12)
BG_ACTIVE, BG_PDN_FAST, BG_PDN_SLOW, BG_PDN_ACT, BG_SR = range(5)
IL_NONE, IL_COL, IL_BANK, IL_BANKCOL = range(4)

LEAVES = ("charge_ma_cycles", "cycles", "avg_current_ma", "energy_pj",
          "time_ns")
PARAM_KEYS = ("datadep", "i2n", "bank_open_delta", "bank_read_factor",
              "bank_write_factor", "q_actpre", "row_ones_slope", "q_ref",
              "i_pd", "io_read_ma_per_one", "io_write_ma_per_zero",
              "ones_quad", "act_surface", "i_pd_slow", "i_actpd", "i_sr")
BFLOAT16 = ml_dtypes.bfloat16


def walk(trace: dict) -> dict:
    """The per-command state of one trace, one command at a time: for
    each command the open banks before it (a bit mask), the background
    state, the interleave mode and the index of the previous RD/WR."""
    cmd = trace["cmd"].tolist()
    bank = trace["bank"].tolist()
    col = trace["col"].tolist()
    n = len(cmd)
    open_mask = [0] * n
    bg = [0] * n
    il = [0] * n
    prev_rw = [-1] * n
    mask = 0
    mode = BG_ACTIVE
    last_rw = -1
    last_bank = -1
    last_col = [-1] * N_BANKS
    for i in range(n):
        c, b = cmd[i], bank[i]
        open_mask[i] = mask
        bg[i] = BG_PDN_ACT if (mode == BG_PDN_FAST and mask) else mode
        prev_rw[i] = last_rw
        if last_rw < 0:
            il[i] = IL_NONE
        elif last_bank == b:
            il[i] = IL_NONE if last_col[b] == col[i] else IL_COL
        else:
            il[i] = IL_BANK if last_col[b] == col[i] else IL_BANKCOL
        if c == ACT:
            mask |= 1 << b
        elif c == PRE:
            mask &= ~(1 << b)
        elif c == PREA:
            mask = 0
        elif c == PDE:
            mode = BG_PDN_FAST
        elif c == PDE_SLOW:
            mode = BG_PDN_SLOW
        elif c == SRE:
            mode = BG_SR
        elif c == PDX or c == SRX:
            mode = BG_ACTIVE
        elif c == RD or c == WR:
            last_rw, last_bank, last_col[b] = i, b, col[i]
    return {"open_mask": np.asarray(open_mask, np.int64),
            "bg": np.asarray(bg, np.int64), "il": np.asarray(il, np.int64),
            "prev_rw": np.asarray(prev_rw, np.int64)}


def _ones(lines: np.ndarray) -> np.ndarray:
    """Ones per 64-byte line: (n, 16) uint32 -> (n,) int64."""
    return np.unpackbits(np.ascontiguousarray(lines).view(np.uint8),
                         axis=1).sum(axis=1, dtype=np.int64)


def charges(trace: dict, params: dict, dtype=np.float64) -> np.ndarray:
    """(n, sets) per-command charge in mA x cycles, computed in ``dtype``
    (accumulations in float32 when ``dtype`` is bfloat16)."""
    st = walk(trace)
    ft = dtype

    def p(key):
        return np.asarray(params[key], np.float64).astype(ft)

    cmd, bank, dt = trace["cmd"], trace["bank"], trace["dt"]
    data = trace["data"]
    is_rw = (cmd == RD) | (cmd == WR)
    op = (cmd == WR).astype(np.int64)
    ones = _ones(data)
    has_prev = st["prev_rw"] >= 0
    prev = np.where(has_prev[:, None], data[np.maximum(st["prev_rw"], 0)],
                    np.uint32(0))
    toggles = np.where(has_prev, _ones(np.bitwise_xor(data, prev)), 0)
    row_ones = np.asarray([bin(r).count("1") for r in trace["row"].tolist()],
                          np.int64)
    open_bits = ((st["open_mask"][:, None] >> np.arange(N_BANKS)) & 1
                 ).astype(ft)                                     # (n, 8)

    # background current of each command's state, per parameter set
    i_up = p("i2n")[None, :] + (open_bits @ p("bank_open_delta").T
                                if ft is np.float64 else
                                _bf16_matmul(open_bits, p("bank_open_delta")))
    bgs = st["bg"][:, None]
    i_bg = np.where(bgs == BG_ACTIVE, i_up,
                    np.where(bgs == BG_PDN_FAST, p("i_pd")[None, :],
                             np.where(bgs == BG_PDN_SLOW,
                                      p("i_pd_slow")[None, :],
                                      np.where(bgs == BG_PDN_ACT,
                                               p("i_actpd")[None, :],
                                               p("i_sr")[None, :]))))
    i_bg = i_bg.astype(ft)
    dtf = dt.astype(ft)[:, None]
    q = i_bg * dtf

    # RD/WR: paper Eq. 2 with the bank factor and the I/O driver current
    coeffs = p("datadep")[:, st["il"], op, :]                  # (sets, n, 3)
    onesf = ones.astype(ft)[None, :]
    togf = toggles.astype(ft)[None, :]
    base = coeffs[..., 0] + coeffs[..., 1] * onesf + coeffs[..., 2] * togf
    base = base + p("ones_quad")[:, None] * coeffs[..., 1] * onesf * (
        onesf / ft(LINE_BITS) - ft(0.5))
    factor = np.where(op[None, :] == 0, p("bank_read_factor")[:, bank],
                      p("bank_write_factor")[:, bank])
    io = np.where(op[None, :] == 0, p("io_read_ma_per_one")[:, None] * onesf,
                  p("io_write_ma_per_zero")[:, None]
                  * (ft(LINE_BITS) - onesf))
    i_rw = (base * factor + io).T                                 # (n, sets)
    burst = np.minimum(dt, T_BURST).astype(ft)[:, None]
    q = q + np.where(is_rw[:, None], (i_rw - i_bg) * burst, ft(0))

    # ACT (+PRE pair) with the row-address and (bank, row-band) factors
    band = trace["row"] >> ROW_BAND_SHIFT
    act_q = p("q_actpre")[None, :] * (
        ft(1) + p("row_ones_slope")[None, :] * row_ones.astype(ft)[:, None])
    act_q = act_q * p("act_surface")[:, bank, band].T
    q = q + np.where((cmd == ACT)[:, None], act_q, ft(0))
    q = q + np.where((cmd == REF)[:, None], p("q_ref")[None, :], ft(0))
    return q.astype(ft)


def _bf16_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(n, 8) x (sets, 8)^T in bfloat16 products, float32 sums."""
    return (a.astype(np.float32) @ b.astype(np.float32).T).astype(BFLOAT16)


def report(trace: dict, params: dict, surface: bool,
           dtype=np.float64) -> dict:
    """The energy report of one trace against every parameter set:
    leaves of shape ``(sets,)``, or ``(sets, 8, 8)`` per (bank, row-band)
    cell with ``surface``."""
    q = charges(trace, params, dtype)
    acc = np.float64 if dtype is np.float64 else np.float32
    q = q.astype(acc)
    dt = trace["dt"].astype(np.int64)
    sets = q.shape[1]
    if surface:
        cell = (trace["bank"].astype(np.int64) * 8
                + (trace["row"] >> ROW_BAND_SHIFT))
        charge = np.zeros((N_CELLS, sets), acc)
        np.add.at(charge, cell, q)
        charge = charge.T.reshape(sets, 8, 8)
        cycles = np.bincount(cell, weights=dt, minlength=N_CELLS
                             ).astype(np.int64).reshape(8, 8)
        cycles = np.broadcast_to(cycles, (sets, 8, 8))
    else:
        charge = q.sum(axis=0)
        cycles = np.full(sets, dt.sum(), np.int64)
    return {"charge_ma_cycles": charge,
            "cycles": cycles,
            "avg_current_ma": charge / np.maximum(cycles, 1),
            "energy_pj": charge * TCK_NS * VDD,
            "time_ns": cycles * TCK_NS}


def gap(answer: dict, ref: dict) -> float:
    """The widest gap between an answer and the reference over every
    leaf, each as a share of that leaf's largest reference value."""
    worst = 0.0
    for leaf in LEAVES:
        a = np.asarray(answer[leaf], np.float64)
        r = np.asarray(ref[leaf], np.float64)
        if a.shape != r.shape:
            return float("inf")
        scale = float(np.max(np.abs(r)))
        d = float(np.max(np.abs(a - r)))
        if not np.isfinite(d):
            return float("inf")
        worst = max(worst, d / scale if scale > 0 else d)
    return worst
