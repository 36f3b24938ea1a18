"""Host time per service window of summing each ticket's rows into its
one report: the program's 'service.combine' spans in the window over the
benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "service.combine", "admit")
