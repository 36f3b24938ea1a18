"""Host time per service window of the engine's program lookup and enqueue:
the program's 'engine.dispatch' spans in the window over the benchmark's
'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "engine.dispatch", "admit")
