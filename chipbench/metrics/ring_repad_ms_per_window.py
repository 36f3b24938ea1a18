"""Host time per service window of the ring's group pick and host re-pad
into its bucket buffers: the program's 'ring.repad' spans in the window
over the benchmark's 'admit' spans."""
from chipbench.program_spans import ms_per


def read(run):
    return ms_per(run, "ring.repad", "admit")
