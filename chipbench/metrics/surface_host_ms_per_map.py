"""Host time per fleet surface map of the program's call
(`fleet_surface_energy`: dispatch and the report's finalization, not the
client's transfer or fetch): the program's 'fleet.surface' spans in the
window over the benchmark's 'dispatch' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "fleet.surface", "dispatch")
