"""Host time per service window of the host fill of the lint's (T, N)
command, bank and dt planes: the program's 'lint.pack' spans in the
window over the benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "lint.pack", "admit")
