"""faults_ms_per_call.whatif: host milliseconds per traced what-if call
spent in ``plan_capacity``'s N+k check, the summed ``repro.plan.faults``
spans of the traced window over the calls it holds.  The span covers every
simulation of the fleet with k replicas down (one a call where the N+k
fleet meets the SLO, more where the loop grows it) and their reads, which
wait for the device."""

import program_spans

SPAN = "repro.plan.faults"
FAMILY = "whatif"


def read(w):
    if w.family != FAMILY or w.trace is None:
        return None
    return program_spans.ms_per_traced_call(w, SPAN)
