"""dispatch_ms_per_call.grid: host milliseconds per traced planning-grid
call spent dispatching its (p, config) batches, the summed
``repro.sweep.dispatch`` spans of the traced window over the calls it
holds.  A dispatch returns once its program is enqueued, so the span is
host work: argument handling, and any tracing or lowering of the stream
program (on a mesh, the ``shard_map`` wrapper built per dispatch)."""

import program_spans

SPAN = "repro.sweep.dispatch"
FAMILY = "grid"


def read(w):
    if w.family != FAMILY or w.trace is None:
        return None
    return program_spans.ms_per_traced_call(w, SPAN)
