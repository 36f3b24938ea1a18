"""Real commands over the command slots of the ring's bucket-shaped
windows (count bucket x length bucket), from the counts of the program's
'ring.repad' spans in the window."""
from chipbench.program_spans import attr_sum, named


def read(run):
    recs = named(run, "ring.repad")
    if recs is None:
        return None
    return 100.0 * attr_sum(recs, "real_cmds") / attr_sum(recs, "slot_cmds")
