"""Ring rows the admitted traces were cut into, per service window: the
'segments' counts of the program's 'trace.segment' spans in the window
over the benchmark's 'admit' spans."""
from chipbench.program_spans import attr_sum, named
from chipbench.readings import window_spans


def read(run):
    recs = named(run, "trace.segment")
    windows = len(window_spans(run, "admit"))
    if recs is None or windows == 0:
        return None
    return float(attr_sum(recs, "segments")) / windows
