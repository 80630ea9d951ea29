"""result_wait_ms_per_call.whatif: host milliseconds per traced what-if
call spent reading the simulated answer back, the summed
``repro.plan.read`` spans of the traced window over the calls it holds.
The reads (mean, p95 and active replicas) block until the device has run
the simulation the call enqueued, so this is where the host waits for the
device."""

import program_spans

SPAN = "repro.plan.read"
FAMILY = "whatif"


def read(w):
    if w.family != FAMILY or w.trace is None:
        return None
    return program_spans.ms_per_traced_call(w, SPAN)
