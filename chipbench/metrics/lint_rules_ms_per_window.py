"""Host time per service window of the lint's jitted rule program, waited
for (host-to-device copy of the planes and device time): the program's
'lint.rules' spans in the window over the benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "lint.rules", "admit")
