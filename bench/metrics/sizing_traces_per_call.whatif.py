"""sizing_traces_per_call.whatif: traces of the sizing bisection per
what-if call in the window, counted by the ``jax.monitoring`` listener on
``/repro/plan/size_traced``, which ``max_rate_under_slo`` records when JAX
traces its scan body.  A bisection built once reads 0; one built anew on
every call reads 1.  Read only where the trace shows the planner's
``repro.plan`` spans."""

import program_spans

EVENT = "/repro/plan/size_traced"
SPAN = "repro.plan"
FAMILY = "whatif"


def read(w):
    if w.family != FAMILY:
        return None
    return program_spans.per_call(w, EVENT, SPAN)
