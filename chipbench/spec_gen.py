"""SPEC CPU2006-mix DRAM command traces, made from a seed.

A copy, kept with the benchmark so that a change to the program cannot
move the yardstick, of the synthetic application model of
``repro.core.traces`` (``SPEC_APPS``, the byte-value distributions,
``TraceBuilder`` and ``app_trace``).  Two things differ from the
original: the random stream is keyed on ``(app, run seed, trace index)``
instead of the app alone, and a trace stops at the last whole request
that fits a target command count instead of taking a request count, so
that a length is asked for once and never searched for.

A trace is a dict of numpy arrays ``cmd, bank, row, col, dt`` (int32,
``(n,)``) and ``data`` (uint32, ``(n, 16)``), with DDR3L-800 timing made
legal by the builder exactly as the original makes it.
"""
from __future__ import annotations

import collections

import numpy as np

# DDR3L-800 geometry, command codes and timing (cycles of tCK = 2.5 ns)
N_BANKS = 8
LINE_BYTES = 64
LINE_WORDS = 16
ROW_BITS = 15
COLS_PER_ROW = 128
NOP, ACT, PRE, RD, WR, REF, PDE, PDX, PREA, PDE_SLOW, SRE, SRX = range(12)
tRCD, tRP, tRAS, tRC, tCCD, tBURST = 6, 6, 14, 20, 4, 4
tRFC, tREFI, tWR, tRTP, tCKE, tXP = 64, 3120, 6, 4, 3, 5
tXPDLL, tXS, tRRD, tFAW, tWTR = 24, 74, 4, 16, 4
_NEG = -(1 << 30)
#: the most commands one request can emit (PRE, ACT, RD/WR, PREA, REF,
#: PREA, entry, NOP, exit, and one NOP of lead time)
MAX_CMDS_PER_REQUEST = 10

FIELDS = ("cmd", "bank", "row", "col", "data", "dt")


class TraceBuilder:
    """Emit-order command builder that lands every command on a
    protocol-legal cycle by stretching the previous slot's ``dt``."""

    def __init__(self):
        self.cmds: list[int] = []
        self.banks: list[int] = []
        self.rows: list[int] = []
        self.cols: list[int] = []
        self.lines: list[int] = []      # request index of the data, or -1
        self.dts: list[int] = []
        self.t = 0
        self.open_row = [-1] * N_BANKS
        self._act_t = [_NEG] * N_BANKS
        self._close_t = [_NEG] * N_BANKS
        self._wr_t = [_NEG] * N_BANKS
        self._rd_t = [_NEG] * N_BANKS
        self._acts = collections.deque(maxlen=4)
        self._last_act = self._last_wr = self._last_rw = _NEG
        self._busy_until = 0
        self._slow_entry = False

    def _earliest(self, c: int, b: int) -> int:
        t = _NEG
        if c != NOP:
            t = max(t, self._busy_until)
        if c == ACT:
            t = max(t, self._close_t[b] + tRP, self._act_t[b] + tRC,
                    self._last_act + tRRD)
            if len(self._acts) == 4:
                t = max(t, self._acts[0] + tFAW)
        elif c == RD or c == WR:
            t = max(t, self._act_t[b] + tRCD, self._last_rw + tCCD)
            if c == RD:
                t = max(t, self._last_wr + tBURST + tWTR)
        elif c == PRE or c == PREA:
            for tb in (range(N_BANKS) if c == PREA else (b,)):
                if self.open_row[tb] >= 0:
                    t = max(t, self._act_t[tb] + tRAS,
                            self._wr_t[tb] + tBURST + tWR,
                            self._rd_t[tb] + tRTP)
        return t

    def emit(self, c, b=0, r=0, co=0, line=-1, dt=0) -> None:
        need = self._earliest(c, b)
        if need > self.t:
            if not self.dts:
                self._append(NOP, 0, 0, 0, -1, need - self.t)
            else:
                self.dts[-1] += need - self.t
            self.t = need
        self.cmds.append(c)
        self.banks.append(b)
        self.rows.append(r)
        self.cols.append(co)
        self.lines.append(line)
        self.dts.append(dt)
        if c == ACT:
            self._act_t[b] = self.t
            self.open_row[b] = r
            self._acts.append(self.t)
            self._last_act = self.t
        elif c == PRE:
            self._close_t[b] = self.t
            self.open_row[b] = -1
        elif c == PREA:
            for tb in range(N_BANKS):
                self._close_t[tb] = self.t
                self.open_row[tb] = -1
        elif c == RD:
            self._rd_t[b] = self.t
            self._last_rw = self.t
        elif c == WR:
            self._wr_t[b] = self.t
            self._last_wr = self.t
            self._last_rw = self.t
        elif c == REF:
            self._busy_until = max(self._busy_until, self.t + tRFC)
        elif c == PDE:
            self._slow_entry = False
        elif c == PDE_SLOW:
            self._slow_entry = True
        elif c == PDX:
            exit_lat = tXPDLL if self._slow_entry else tXP
            self._busy_until = max(self._busy_until, self.t + exit_lat)
        elif c == SRX:
            self._busy_until = max(self._busy_until, self.t + tXS)
        self.t += int(dt)

    def _append(self, c, b, r, co, line, dt) -> None:
        self.cmds.append(c)
        self.banks.append(b)
        self.rows.append(r)
        self.cols.append(co)
        self.lines.append(line)
        self.dts.append(dt)


# ---------------------------------------------------------------------------
# byte-value distributions ("what the data looks like")
# ---------------------------------------------------------------------------
def _dist_zeros():
    p = np.full(256, 0.0008)
    p[0x00] = 0.70
    p[0xFF] = 0.05
    p[0x01] = 0.05
    return p / p.sum()


def _dist_ascii():
    p = np.full(256, 0.0004)
    for c in range(0x61, 0x7B):
        p[c] = 0.025
    p[0x20] = 0.12
    for c in range(0x41, 0x5B):
        p[c] = 0.004
    for c in range(0x30, 0x3A):
        p[c] = 0.006
    p[0x0A] = 0.01
    return p / p.sum()


def _dist_int_small():
    p = np.full(256, 0.0008)
    for v, w in ((0x00, 0.32), (0x01, 0.06), (0x02, 0.03), (0x03, 0.02),
                 (0xFF, 0.24), (0xFE, 0.05), (0xFD, 0.02), (0x04, 0.01),
                 (0x08, 0.01), (0x7F, 0.02)):
        p[v] = w
    return p / p.sum()


def _dist_fp32():
    p = np.full(256, 0.002)
    for v, w in ((0x3F, 0.12), (0xBF, 0.10), (0x40, 0.06), (0xC0, 0.05),
                 (0x3E, 0.05), (0xBE, 0.04), (0x00, 0.08), (0x80, 0.03),
                 (0x7F, 0.03)):
        p[v] = w
    return p / p.sum()


def _dist_pointer():
    p = np.full(256, 0.0015)
    p[0x00] = 0.26
    p[0x7F] = 0.14
    p[0xFF] = 0.06
    p[0x55] = 0.04
    for v in range(0x10, 0x90, 0x08):
        p[v] = 0.01
    return p / p.sum()


def _dist_random():
    return np.full(256, 1.0 / 256)


BYTE_DISTS = {"zeros": _dist_zeros, "ascii": _dist_ascii,
              "int_small": _dist_int_small, "fp32": _dist_fp32,
              "pointer": _dist_pointer, "random": _dist_random}

#: (name, intensity, row_hit, read_frac, data_dist, app seed): the 23
#: synthetic applications spanning the paper's SPEC CPU2006 suite (Fig 25)
SPEC_APPS = (
    ("perlbench", 0.16, 0.75, 0.70, "ascii", 1),
    ("bzip2", 0.30, 0.55, 0.60, "random", 2),
    ("gcc", 0.25, 0.65, 0.65, "pointer", 3),
    ("mcf", 0.75, 0.25, 0.75, "pointer", 4),
    ("gobmk", 0.12, 0.70, 0.68, "int_small", 5),
    ("hmmer", 0.22, 0.90, 0.55, "int_small", 6),
    ("sjeng", 0.10, 0.72, 0.66, "int_small", 7),
    ("libquantum", 0.82, 0.95, 0.80, "zeros", 8),
    ("h264ref", 0.26, 0.88, 0.58, "int_small", 9),
    ("omnetpp", 0.55, 0.30, 0.70, "pointer", 10),
    ("astar", 0.45, 0.45, 0.72, "pointer", 11),
    ("xalancbmk", 0.50, 0.40, 0.74, "ascii", 12),
    ("bwaves", 0.72, 0.90, 0.65, "fp32", 13),
    ("gamess", 0.08, 0.82, 0.60, "fp32", 14),
    ("milc", 0.70, 0.82, 0.62, "fp32", 15),
    ("zeusmp", 0.50, 0.85, 0.61, "fp32", 16),
    ("gromacs", 0.18, 0.74, 0.63, "fp32", 17),
    ("cactusADM", 0.62, 0.86, 0.55, "fp32", 18),
    ("leslie3d", 0.66, 0.86, 0.60, "fp32", 19),
    ("namd", 0.10, 0.80, 0.64, "fp32", 20),
    ("soplex", 0.64, 0.35, 0.73, "fp32", 21),
    ("povray", 0.07, 0.78, 0.62, "fp32", 22),
    ("lbm", 0.85, 0.93, 0.50, "fp32", 23),
)


def sample_lines(dist_name: str, n_lines: int,
                 rng: np.random.Generator) -> np.ndarray:
    """(n_lines, 16) uint32 lines with bytes drawn from the distribution."""
    b = rng.choice(256, size=(n_lines, LINE_BYTES),
                   p=BYTE_DISTS[dist_name]()).astype(np.uint32)
    return (b[:, 0::4] | (b[:, 1::4] << 8) | (b[:, 2::4] << 16)
            | (b[:, 3::4] << 24)).astype(np.uint32)


def app_trace(app: int, max_commands: int, seed: int, index: int) -> dict:
    """The trace of SPEC app ``app`` (an index of :data:`SPEC_APPS`):
    whole requests while they fit ``max_commands`` commands.  The random
    stream is keyed on (app seed, ``seed``, ``index``)."""
    if max_commands < MAX_CMDS_PER_REQUEST:
        raise ValueError(f"max_commands={max_commands} holds no request")
    _, intensity, row_hit, read_frac, dist, app_seed = SPEC_APPS[app]
    rng = np.random.default_rng(
        np.random.SeedSequence([29, app_seed, int(seed) & (2**63 - 1),
                                int(index)]))
    n_req = max_commands            # a request emits at least one command
    mean_gap = tBURST * (1.0 - intensity) / max(intensity, 0.01)
    bank_seq = rng.integers(0, N_BANKS, size=n_req).tolist()
    hit_seq = (rng.random(n_req) < row_hit).tolist()
    rd_seq = (rng.random(n_req) < read_frac).tolist()
    row_seq = rng.integers(0, 1 << ROW_BITS, size=n_req).tolist()
    col_seq = rng.integers(0, COLS_PER_ROW, size=n_req).tolist()
    gap_seq = (rng.geometric(1.0 / (1.0 + mean_gap), size=n_req) - 1
               ).tolist()

    bld = TraceBuilder()
    ref_anchor = 0
    for i in range(n_req):
        if len(bld.cmds) + MAX_CMDS_PER_REQUEST > max_commands:
            break
        b = bank_seq[i]
        if hit_seq[i] and bld.open_row[b] >= 0:
            r = bld.open_row[b]
        else:
            r = row_seq[i]
            if bld.open_row[b] >= 0:
                bld.emit(PRE, b, dt=tRP)
            bld.emit(ACT, b, r, dt=tRCD)
        op = RD if rd_seq[i] else WR
        gap = gap_seq[i]
        if gap > 128:
            # long idle: the deepest low-power state the gap can absorb
            if gap > 2048:
                entry, exit_cmd, exit_dt = SRE, SRX, tXS
            elif gap > 512:
                entry, exit_cmd, exit_dt = PDE_SLOW, PDX, tXPDLL
            else:
                entry, exit_cmd, exit_dt = PDE, PDX, tXP
            bld.emit(op, b, r, col_seq[i], i, dt=tBURST)
            bld.emit(PREA, dt=tRP)
            if (entry != SRE
                    and bld.t - ref_anchor + tCKE + gap + exit_dt >= tREFI):
                bld.emit(REF, dt=tRFC)
                bld.emit(PREA, dt=0)
                ref_anchor = bld.t
            bld.emit(entry, dt=tCKE)
            bld.emit(NOP, dt=gap)
            bld.emit(exit_cmd, dt=exit_dt)
            if entry == SRE:
                ref_anchor = bld.t
            continue
        bld.emit(op, b, r, col_seq[i], i, dt=tBURST + gap)
        if bld.t - ref_anchor >= tREFI:
            bld.emit(PREA, dt=tRP)
            bld.emit(REF, dt=tRFC)
            ref_anchor = bld.t

    line_idx = np.asarray(bld.lines, np.int64)
    lines = sample_lines(dist, int(line_idx.max()) + 1, rng)
    data = np.where((line_idx >= 0)[:, None], lines[np.maximum(line_idx, 0)],
                    np.uint32(0)).astype(np.uint32)
    return {"cmd": np.asarray(bld.cmds, np.int32),
            "bank": np.asarray(bld.banks, np.int32),
            "row": np.asarray(bld.rows, np.int32),
            "col": np.asarray(bld.cols, np.int32),
            "data": data,
            "dt": np.asarray(bld.dts, np.int32)}


def log_uniform_lengths(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` command counts at the quantiles (i + 1/2)/n of the
    log-uniform law over [lo, hi]: the same set for every seed."""
    q = (np.arange(n) + 0.5) / n
    return np.floor(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
                    ).astype(np.int64)


def trace_pool(n: int, lo: int, hi: int, seed: int) -> list[dict]:
    """``n`` SPEC-mix traces: the apps in turn and the lengths of
    :func:`log_uniform_lengths`, each list shuffled by ``seed``, so a
    seed changes which app gets which length and the data, never the
    set of sizes."""
    rng = np.random.default_rng(np.random.SeedSequence([31, int(seed)]))
    apps = rng.permutation(np.arange(n) % len(SPEC_APPS))
    lengths = rng.permutation(log_uniform_lengths(n, lo, hi))
    return [app_trace(int(a), int(m), seed, k)
            for k, (a, m) in enumerate(zip(apps, lengths))]
