"""engine_busy_ms_per_mquery.grid: device-busy milliseconds, summed over
the cell's chips (the union of the op intervals of each chip's trace),
per million simulated queries of the traced calls: what the stream
engine costs the device per unit of planning work."""

import trace_reduce

FAMILY = "grid"


def read(w):
    if w.family != FAMILY or w.trace is None:
        return None
    win = w.trace["window"]
    busy_ns = sum(trace_reduce.busy_ns(d, win) for d in w.trace_devices())
    if busy_ns == 0:
        return None
    return busy_ns / 1e6 / (w.n_traced * w.work / 1e6)
