"""Host time per service window of the lint's three (T, R, N) outputs
fetched to the host: the program's 'lint.fetch' spans in the window over
the benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "lint.fetch", "admit")
