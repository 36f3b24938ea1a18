"""Host time per service window of cutting the admitted traces into ring
rows and scanning their carries: the program's 'trace.segment' spans in
the window over the benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "trace.segment", "admit")
