"""Host time per service window of the ring's bucket buffers sent to the
device: the program's 'ring.transfer' spans in the window over the
benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "ring.transfer", "admit")
