"""Host time per service window of the per-ticket rows of the report,
fetched and sliced: the program's 'service.slice' spans in the window
over the benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "service.slice", "admit")
