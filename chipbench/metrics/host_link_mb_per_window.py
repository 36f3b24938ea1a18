"""Megabytes (1e6 bytes) a service window moves over the host link: the
lint's planes to the device ('lint.rules') and its outputs back
('lint.fetch'), the ring's buffers to the device ('ring.transfer') and
the report back ('service.slice'), summed over the window's program
spans and divided by the benchmark's 'admit' spans."""
from chipbench.program_spans import attr_sum, named
from chipbench.readings import window_spans

SPANS = ("lint.rules", "lint.fetch", "ring.transfer", "service.slice")


def read(run):
    sent = named(run, "ring.transfer")
    windows = len(window_spans(run, "admit"))
    if sent is None or windows == 0:
        return None
    return sum(attr_sum(named(run, name) or [], "bytes")
               for name in SPANS) / 1e6 / windows
