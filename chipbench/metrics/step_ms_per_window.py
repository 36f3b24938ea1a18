"""Mean host time of one dispatch round (`step` until the ring is
empty: re-pad, transfer, engine dispatch, block, per-ticket slice) in
the window ('step' spans)."""
from chipbench.readings import mean_span_ms


def read(run):
    return mean_span_ms(run, "step")
