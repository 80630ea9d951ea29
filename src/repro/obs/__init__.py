"""Observability for the simulated search engine.

Three layers, one per way of looking at a running cluster:

  * `repro.obs.timeline` — streaming per-time-bin telemetry
    (:class:`TelemetrySpec` / :class:`Timeline`), accumulated inside the
    simulator's scan carry and self-checkable against the operational
    laws U = X*S and L = lambda*W.
  * `repro.obs.trace_export` — span traces: a tapped/simulated sample
    path rendered as Chrome-trace JSON (open in chrome://tracing or
    Perfetto) showing the broker -> fork -> join structure per query.
  * `repro.obs.profile` — XLA-level profiling hooks: compile time,
    `cost_analysis()` flops/bytes and `memory_analysis()` peaks of the
    kernel stack and entry points, as structured `ProfileRecord`s that
    the benchmarks embed in every BENCH_*.json.

``python -m repro.obs.report`` renders all three as a text dashboard.

`repro.obs.spans` instruments the planner itself rather than the
simulated cluster: ``repro.*`` host spans in a ``jax.profiler`` trace and
``/repro/*`` ``jax.monitoring`` events when a step is traced.

Import discipline: this package root re-exports ONLY the timeline layer
— `repro.core.simulator` imports it, so anything heavier (trace export
and profiling import calibrate/kernels, which import the simulator)
must stay behind its own submodule import to keep the import graph
acyclic.
"""

from repro.obs.timeline import (  # noqa: F401
    DEFAULT_TIMELINE_BINS,
    TelemetrySpec,
    Timeline,
    timeline_from_trace,
)

__all__ = ["TelemetrySpec", "Timeline", "timeline_from_trace",
           "DEFAULT_TIMELINE_BINS"]
