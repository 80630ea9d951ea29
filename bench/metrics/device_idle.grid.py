"""device_idle.grid: the share of the traced window, in percent, in which
the cell's chips ran no operation (1 - busy / window, averaged over the
chips), in a planning-grid cell."""

import trace_reduce

FAMILY = "grid"


def read(w):
    if w.family != FAMILY or w.trace is None:
        return None
    return trace_reduce.idle_percent(w.trace_devices(), w.trace["window"])
