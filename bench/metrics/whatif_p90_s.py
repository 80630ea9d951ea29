"""whatif_p90_s: the 90th percentile of the wall latency of every what-if
call in the window (numpy's linear interpolation between order
statistics)."""

import numpy as np

FAMILY = "whatif"


def read(w):
    if w.family != FAMILY:
        return None
    return float(np.percentile(w.latencies, 90))
