"""traces_per_call.whatif: jaxpr traces per what-if call in the window,
counted by a ``jax.monitoring`` listener on
``/jax/core/compile/jaxpr_trace_duration``.  ``plan_capacity`` runs its
Section 6 bisection as an eager ``lax.scan`` over a function it builds on
every call, so every call traces again."""

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
FAMILY = "whatif"


def read(w):
    if w.family != FAMILY:
        return None
    return w.counters.get(TRACE_EVENT, 0) / w.n_calls
