"""engine_traces_per_call.grid: traces of the stream engine per
planning-grid call in the window, counted by the ``jax.monitoring``
listener on ``/repro/stream/traced``, which ``_simulate_stream`` records
when JAX traces it.  A warm grid reads 0; one whose dispatches retrace the
engine reads one per retraced dispatch.  Read only where the trace shows
the planner's ``repro.grid`` spans."""

import program_spans

EVENT = "/repro/stream/traced"
SPAN = "repro.grid"
FAMILY = "grid"


def read(w):
    if w.family != FAMILY:
        return None
    return program_spans.per_call(w, EVENT, SPAN)
