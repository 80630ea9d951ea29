"""whatif_mean_s: the window's wall time over the number of what-if calls
it completed."""


def read(w):
    if w.kind != "whatif":
        return None
    return w.window_s / w.n_calls
