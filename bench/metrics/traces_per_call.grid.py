"""traces_per_call.grid: jaxpr traces per planning-grid call in the window,
counted by a ``jax.monitoring`` listener on
``/jax/core/compile/jaxpr_trace_duration``.  A program that is retraced on
every call (a ``shard_map`` built anew per dispatch) pays its tracing on
every call even when the compilation cache holds it."""

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
FAMILY = "grid"


def read(w):
    if w.family != FAMILY:
        return None
    return w.counters.get(TRACE_EVENT, 0) / w.n_calls
