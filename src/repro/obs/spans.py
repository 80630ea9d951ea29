"""Host spans and trace counters at the planner's own boundaries.

Two helpers, and no store of their own:

* :func:`span` is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``.
  The profiler keeps it, on the same clock as the device's operations,
  while a trace is being taken, and it costs about a microsecond when none
  is.  Spans of one planning call share their identity by nesting on the
  calling thread.
* :func:`count` records the ``jax.monitoring`` event ``/repro/<event>``.
  Called inside a function that JAX traces, it fires once per trace, so a
  listener counts the retraces of that step.

The device side is named by ``jax.named_scope("stream.<stage>")`` in the
stream engine, which costs nothing at run time: the scope is metadata of
the compiled operations.
"""

from __future__ import annotations

import jax

__all__ = ["span", "count"]


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A profiler span ``repro.<name>``; ``meta`` becomes its annotation."""
    return jax.profiler.TraceAnnotation("repro." + name, **meta)


def count(event: str) -> None:
    """Record the monitoring event ``/repro/<event>``."""
    jax.monitoring.record_event("/repro/" + event)
