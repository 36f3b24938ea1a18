"""95th percentile, over every request due in the window, of the time
from when it was due to when its answer came back (host clock)."""
import numpy as np


def read(run):
    lat = run.outcome.latencies_s
    return float(np.percentile(lat, 95)) * 1e3 if len(lat) else None
