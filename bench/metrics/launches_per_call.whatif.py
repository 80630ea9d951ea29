"""launches_per_call.whatif: device program executions per what-if call,
counted from the ``XLA Modules`` line of the chip's trace of the window's
traced calls.  ``plan_capacity`` runs eager analytic ops and host syncs
around its one simulation, and every eager op is a launch of its own."""

import trace_reduce

FAMILY = "whatif"


def read(w):
    if w.family != FAMILY or w.trace is None:
        return None
    win = w.trace["window"]
    n = sum(trace_reduce.executions(d, win) for d in w.trace_devices())
    return n / w.n_traced if n else None
