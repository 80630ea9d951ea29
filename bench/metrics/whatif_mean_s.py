"""whatif_mean_s: the window's wall time over the number of what-if calls
it completed."""

FAMILY = "whatif"


def read(w):
    if w.family != FAMILY:
        return None
    return w.window_s / w.n_calls
