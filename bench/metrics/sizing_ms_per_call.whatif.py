"""sizing_ms_per_call.whatif: host milliseconds per traced what-if call
spent in ``plan_capacity``'s sizing step, the summed ``repro.plan.size``
spans of the traced window over the calls it holds.  The span covers the
Eq 7/8 bisection (``replicas_needed``), the bounds, the utilization and
their reads to the host, up to the simulation."""

import program_spans

SPAN = "repro.plan.size"
FAMILY = "whatif"


def read(w):
    if w.family != FAMILY or w.trace is None:
        return None
    return program_spans.ms_per_traced_call(w, SPAN)
