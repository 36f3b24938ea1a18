"""Host time per request of the lint's jitted rule program, waited for:
the program's 'lint.rules' spans in the window over the benchmark's
'admit' spans (one per request)."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "lint.rules", "admit")
