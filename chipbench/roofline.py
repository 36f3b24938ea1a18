"""The bytes the estimation needs, counted over real commands only.

Per real command: its 64-byte data line once, the five int32 fields the
charge reads (command, bank, row, column, cycles), the structural ACT
factor of every parameter set (f32), and with the surface the int32 cell
index.  Per answer (trace) and parameter set: the five f32 report
leaves, for each of the 64 (bank, row-band) cells with the surface.
Padding slots, re-reads and intermediate planes are what an
implementation adds, so they count against its share.

The operations are not the bound: a command costs some tens of flops
per parameter set, under a thousandth of the time its bytes take at the
peaks of ``peaks.json``.
"""
from __future__ import annotations

LINE_BYTES = 64
FIELD_BYTES = 5 * 4
FACTOR_BYTES = 4
CELL_BYTES = 4
REPORT_BYTES = 5 * 4
CELLS = 64


def needed_bytes(counters: dict) -> float:
    sets = counters["sets"]
    surface = counters["surface"]
    per_command = (LINE_BYTES + FIELD_BYTES + FACTOR_BYTES * sets
                   + (CELL_BYTES if surface else 0))
    per_answer = REPORT_BYTES * sets * (CELLS if surface else 1)
    return (counters["real_commands"] * per_command
            + counters["answers"] * per_answer)
