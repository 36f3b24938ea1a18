"""Mean host time of one admission call (`submit_many` / `submit`:
lint gate and ring admission) in the window ('admit' spans)."""
from chipbench.readings import mean_span_ms


def read(run):
    return mean_span_ms(run, "admit")
