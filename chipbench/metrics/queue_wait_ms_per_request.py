"""Mean time a request waited in the ring, from its admission to the take
of its window, from the counts of the program's 'ring.repad' spans in the
window."""
from chipbench.program_spans import attr_sum, named


def read(run):
    recs = named(run, "ring.repad")
    if recs is None:
        return None
    return 1e3 * attr_sum(recs, "wait_s") / attr_sum(recs, "n_real")
