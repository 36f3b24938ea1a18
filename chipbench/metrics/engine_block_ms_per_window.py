"""Host time per service window of the wait for the device to finish the
estimate: the program's 'engine.block' spans in the window over the
benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "engine.block", "admit")
