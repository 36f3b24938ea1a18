"""Host time per service window of the host's walk of the fired rules into
diagnostics: the program's 'lint.extract' spans in the window over the
benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "lint.extract", "admit")
