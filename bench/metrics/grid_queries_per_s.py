"""grid_queries_per_s: simulated queries of every planning-grid call in the
window (scenarios x queries per call), over the window's wall time, first
call's start to last call's end."""

FAMILY = "grid"


def read(w):
    if w.family != FAMILY:
        return None
    return w.n_calls * w.work / w.window_s
