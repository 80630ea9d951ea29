"""The planner's own instrumentation (`repro.obs.spans`) lands where it says.

* host spans: a traced ``plan_capacity`` call holds one ``repro.plan``
  that encloses its sizing, simulation dispatch and result read, in that
  order, on the calling thread; a traced ``plan_over_grid`` holds one
  ``repro.sweep.dispatch`` per (p, r) batch, annotated with both;
* trace counters: ``/repro/plan/size_traced`` fires when the sizing
  program of ``plan_capacity`` is traced, once per input structure and
  not again for other rates, SLOs or keys, and ``/repro/stream/traced``
  only when the stream engine is traced;
* device scopes: the stream engine's lowered text names every
  ``stream.<stage>`` that its options turn on.

The scopes are metadata: the bit-identity tests of ``telemetry=None``
(test_obs.py) and of the result cache (test_replication.py) run on the
scoped program.
"""

from __future__ import annotations

import collections
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import capacity, planner, simulator, sweep
from repro.core.cluster import ClusterSpec
from repro.core.faults import FaultSpec
from repro.launch.elastic import AutoscalePolicy
from repro.obs import spans
from repro.obs.timeline import TelemetrySpec

PARAMS = capacity.scenario("memory+cpus+disks")
T5 = capacity.TABLE5_PARAMS


def _host_spans(trace_dir) -> list:
    """The ``repro.*`` host spans of a trace: (name, start, end, thread,
    {stat: value})."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, line.name,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("repro.")]
    return out


def test_span_and_count_helpers(events):
    with spans.span("probe", p=3):
        spans.count("probe/event")
    assert events["/repro/probe/event"] == 1


def test_plan_capacity_spans_nest_in_order(tmp_path):
    def plan():
        return capacity.plan_capacity(PARAMS, 150.0, 0.3, simulate=True,
                                      key=jax.random.PRNGKey(1),
                                      n_queries=2000)

    plan()                                  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    plan()
    jax.profiler.stop_trace()
    found = _host_spans(tmp_path)
    outer = [s for s in found if s[0] == "repro.plan"]
    assert len(outer) == 1
    _, lo, hi, thread, _ = outer[0]
    inner = sorted((s for s in found if s[0] != "repro.plan"),
                   key=lambda s: s[1])
    assert [s[0] for s in inner] == ["repro.plan.size",
                                     "repro.plan.simulate",
                                     "repro.plan.read"]
    for name, s, e, th, _ in inner:
        assert th == thread and lo <= s <= e <= hi, name
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def test_plan_over_grid_one_dispatch_span_per_batch(tmp_path):
    grid = sweep.SweepGrid.build(lam=[20.0, 40.0], p=[4.0, 8.0],
                                 cpu=[1.0], disk=[1.0], hit=[T5.hit],
                                 r=[1.0, 2.0], base=T5,
                                 broker_from_p=False)

    def plan():
        res, fr = planner.plan_over_grid(
            grid, 0.5, simulate=True, key=jax.random.PRNGKey(2),
            n_queries=1024, chunk_size=512, quantile=0.95)
        jax.block_until_ready((res.stats, fr.cost))

    plan()
    jax.profiler.start_trace(str(tmp_path))
    plan()
    jax.profiler.stop_trace()
    found = _host_spans(tmp_path)
    names = collections.Counter(s[0] for s in found)
    assert names["repro.grid"] == 1
    assert names["repro.sweep.gather"] == names["repro.frontier"] == 1
    dispatch = [s[4] for s in found if s[0] == "repro.sweep.dispatch"]
    assert sorted((d["p"], d["r"]) for d in dispatch) == [
        (4, 1), (4, 2), (8, 1), (8, 2)]


def test_size_traced_once_per_plan_call(events):
    def plan(rate, slo, seed):
        capacity.plan_capacity(PARAMS, rate, slo, simulate=True,
                               key=jax.random.PRNGKey(seed),
                               n_queries=2000)

    plan(200.0, 0.3, 0)
    first = events["/repro/plan/size_traced"]
    for rate, slo, seed in ((190.0, 0.31, 1), (210.0, 0.32, 2),
                            (180.0, 0.29, 3)):
        plan(rate, slo, seed)
    assert first <= 1
    assert events["/repro/plan/size_traced"] == first


def test_stream_traced_only_when_the_engine_is_traced(events):
    def sim(seed, n_queries=1536):
        return simulator.simulate_fork_join(
            jax.random.PRNGKey(seed), 40.0, n_queries, T5, chunk_size=512)

    sim(0).mean_response.block_until_ready()
    first = events["/repro/stream/traced"]
    sim(1).mean_response.block_until_ready()
    assert events["/repro/stream/traced"] == first
    sim(1, n_queries=2048).mean_response.block_until_ready()  # new static
    assert events["/repro/stream/traced"] == first + 1
    assert first >= 1


ALWAYS = {"draws", "broker", "server", "join", "stats"}


@pytest.mark.parametrize("cluster,telemetry,stages", [
    (ClusterSpec(), None, ALWAYS),
    (ClusterSpec(r=2), None, ALWAYS | {"route"}),
    (ClusterSpec(r=2, replica_impl="masked"), None, ALWAYS | {"route"}),
    (ClusterSpec(r=3, routing="jsq", result_cache=(0.5, 0.069e-3)),
     TelemetrySpec(n_bins=4), ALWAYS | {"route", "cache", "telemetry"}),
    (ClusterSpec(autoscale=AutoscalePolicy(
        min_r=1, max_r=2, decision_interval_seconds=0.5)), None,
     ALWAYS | {"route", "autoscale"}),
    (ClusterSpec(r=2, fault=FaultSpec(outages=((1, 0.0, 5.0),),
                                      hedge_after_seconds=0.05)), None,
     ALWAYS | {"route", "fault"}),
], ids=["r1", "round_robin", "masked", "jsq_cache_telemetry", "autoscale",
        "fault"])
def test_lowered_engine_names_its_stages(cluster, telemetry, stages):
    hit, s_cache, has_cache = simulator._cache_args(cluster.result_cache)
    lowered = simulator._simulate_stream.lower(
        jax.random.PRNGKey(0),
        simulator._as_batch_process(jnp.asarray([20.0, 40.0])),
        simulator._vec_params(T5), hit, s_cache, 1536, int(T5.p),
        "exponential", "xla", 512, 0.1, 64, r=cluster.engine_r,
        routing=cluster.routing, has_cache=has_cache,
        replica_impl=cluster.replica_impl, autoscale=cluster.autoscale,
        telemetry=telemetry, fault=cluster.fault)
    text = lowered.as_text(debug_info=True)
    assert set(re.findall(r"stream\.([a-z]+)", text)) == stages
