"""faulted_sims_per_call.whatif: simulations of the fleet with k replicas
down per what-if call in the window, counted by the ``jax.monitoring``
listener on ``/repro/plan/faulted_sim``, which ``plan_capacity`` records
once per faulted simulation it runs.  An N+k fleet that meets the SLO at
once reads 1; every replica the loop adds reads one more.  Read only where
the trace shows the planner's ``repro.plan.faults`` spans (a call with
``survive_faults``)."""

import program_spans

EVENT = "/repro/plan/faulted_sim"
SPAN = "repro.plan.faults"
FAMILY = "whatif"


def read(w):
    if w.family != FAMILY:
        return None
    return program_spans.per_call(w, EVENT, SPAN)
