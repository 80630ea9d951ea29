"""Fault injection, failover routing and graceful degradation (ISSUE 10).

Five contracts:

* zero-cost identity — ``ClusterSpec(fault=None)`` and an all-up
  `FaultSpec` (no outages, slowdown factors of 1, never-firing broker
  timeout and hedge) are BIT-IDENTICAL to the pre-fault engine in every
  shared statistic, across routing policies;
* chunking invariance — `fault_scan`'s outage-mask recurrence threads
  its carry through arbitrary block splits with identical per-query
  masks (hypothesis property, mirroring tests/test_autoscale.py);
* failover semantics — a replica in an outage window receives no
  queries, its share spills to the survivors (``spill_fraction`` > 0,
  ``availability`` = 1 while any replica survives; with ALL replicas
  down arrivals are counted unavailable), and round-robin splits the
  stream evenly over the survivors;
* degraded operation — a broker timeout with k-of-p quorum caps the
  join, degraded responses are counted, and hedged retries can only
  help (p95 never worse than the unhedged twin on the same draws);
* plan conservativeness — ``plan_capacity(survive_faults=k)`` never
  provisions fewer replicas than the fault-free plan and records the
  simulated p95 of the k-down scenario; it runs a spec's own timeout,
  quorum and hedging under the outages, grows the fleet until the
  faulted p95 holds (or warns at the cap), and every simulated number
  of the plan describes the fleet it returns.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import capacity, simulator
from repro.core.cluster import ClusterSpec
from repro.core.faults import FaultSpec, fault_init, fault_scan
from repro.core.queueing import ServerParams

PARAMS = ServerParams(p=4, s_broker=0.004, s_hit=0.0125, s_miss=0.05,
                      s_disk=0.04, hit=0.5)
KEY = jax.random.PRNGKey(42)

# statistics the fault-free and all-up programs must share bitwise
SHARED = ("count", "sum_response", "sumsq_response", "sum_broker",
          "sum_cluster", "sum_server", "hist", "tap_response")

ALL_UP = FaultSpec(degraded=((0, 1.0), (2, 1.0)),
                   broker_timeout_seconds=1e9, quorum_k=1,
                   hedge_after_seconds=1e9, hedge_attempts=2)


def run(fault, *, routing="round_robin", r=3, n=4_000, rate=60.0,
        key=KEY, chunk_size=512, **kw):
    return simulator.simulate_fork_join(
        key, rate, n, PARAMS, chunk_size=chunk_size,
        cluster=ClusterSpec(r=r, routing=routing, fault=fault), **kw)


# ---------------------------------------------------------------- identity

@pytest.mark.parametrize("routing", ["round_robin", "random", "jsq"])
def test_fault_none_and_all_up_bit_identical(routing):
    """ACCEPTANCE: the fault machinery costs nothing when nothing can
    fail — fault=None and the all-up spec produce bit-identical shared
    statistics under every routing policy."""
    a = run(None, routing=routing, tap_size=16)
    b = run(ALL_UP, routing=routing, tap_size=16)
    for name in SHARED:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{routing}: all-up FaultSpec perturbed {name}")
    # the all-up run still reports its (empty) fault channels
    assert a.spill_count is None and b.spill_count is not None
    assert float(b.availability) == 1.0
    assert float(b.spill_fraction) == 0.0


def test_fault_none_matches_missing_spec_exactly():
    a = simulator.simulate_fork_join(
        KEY, 60.0, 2_000, PARAMS, chunk_size=512, cluster=ClusterSpec(r=2))
    b = run(None, r=2, n=2_000)
    for name in SHARED[:-1]:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)))


# ---------------------------------------------------------------- failover

def test_outage_spills_to_survivors():
    horizon = 4_000 / 60.0
    down = FaultSpec(outages=((1, 0.0, horizon),))  # replica 1 out all run
    res = run(down)
    assert float(res.availability) == 1.0      # survivors always existed
    assert float(res.spill_fraction) > 0.2     # its share moved over
    assert float(res.unavail_count) == 0.0
    # round_robin sends ~1/3 of arrivals to the dead replica's slot
    assert abs(float(res.spill_fraction) - 1.0 / 3.0) < 0.1


@pytest.mark.parametrize("first_chunk", [0, 7])
def test_round_robin_failover_splits_evenly(first_chunk):
    """With replica 0 of 4 down, round-robin sends query g to the
    (g mod 3)-th survivor: each gets a third of a block to within one
    query, and ``spill`` marks exactly the queries whose raw replica
    (g mod 4) is down."""
    chunk = 601
    gidx = first_chunk * chunk + jnp.arange(chunk)
    up = jnp.ones((1, chunk, 4), bool).at[:, :, 0].set(False)
    assign, spill, unavail = simulator._routing_assign(
        "round_robin", 4, KEY, first_chunk, gidx, 1, chunk, up=up)
    counts = np.bincount(np.asarray(assign[0]), minlength=4)
    assert counts[0] == 0
    assert counts[1:].max() - counts[1:].min() <= 1
    np.testing.assert_array_equal(np.asarray(assign[0]),
                                  1 + np.asarray(gidx) % 3)
    np.testing.assert_array_equal(np.asarray(spill[0]),
                                  np.asarray(gidx) % 4 == 0)
    assert not np.asarray(unavail).any()


def test_all_replicas_down_counts_unavailable():
    horizon = 4_000 / 60.0
    dead = FaultSpec(outages=tuple((j, 0.0, horizon) for j in range(3)))
    res = run(dead)
    assert float(res.availability) < 0.05
    assert float(res.unavail_count) > 0


def test_jsq_masks_down_replica():
    horizon = 4_000 / 60.0
    down = FaultSpec(outages=((0, 0.0, horizon),))
    res = run(down, routing="jsq")
    assert float(res.availability) == 1.0
    assert float(res.spill_fraction) > 0.2


def test_windowed_outage_only_affects_window():
    res_win = run(FaultSpec(outages=((0, 5.0, 10.0),)))
    res_always = run(FaultSpec(outages=((0, 0.0, 1e9),)))
    assert (0.0 < float(res_win.spill_fraction)
            < float(res_always.spill_fraction))


def test_mtbf_process_churns_and_repairs():
    res = run(FaultSpec(mtbf_seconds=5.0, mttr_seconds=1.0))
    # failures happened, but repairs kept availability high
    assert 0.0 < float(res.spill_fraction) < 0.5
    assert float(res.availability) > 0.9


# ------------------------------------------------------------- degradation

def test_quorum_timeout_caps_join_and_counts_degraded():
    slow = dataclasses.replace(PARAMS, hit=0.0)
    deadline = 0.08
    spec = ClusterSpec(r=1, fault=FaultSpec(
        broker_timeout_seconds=deadline, quorum_k=2))
    base = simulator.simulate_fork_join(KEY, 20.0, 3_000, slow,
                                        chunk_size=512,
                                        cluster=ClusterSpec(r=1))
    capped = simulator.simulate_fork_join(KEY, 20.0, 3_000, slow,
                                          chunk_size=512, cluster=spec)
    assert float(capped.degraded_fraction) > 0.1
    assert float(capped.mean_response) < float(base.mean_response)
    # quorum can cut short but never lengthen a response
    assert float(capped.quantile(0.99)) <= float(base.quantile(0.99)) + 1e-6


def test_degraded_server_slows_the_join():
    fast = run(None, n=3_000)
    slow = run(FaultSpec(degraded=((1, 4.0),)), n=3_000)
    assert float(slow.mean_response) > float(fast.mean_response)
    # slowdown factor 1 is a no-op (covered bitwise above); factor > 1
    # must not touch the fault counters
    assert float(slow.spill_fraction) == 0.0


def test_hedging_never_hurts():
    slow = dataclasses.replace(PARAMS, hit=0.0)

    def go(fault):
        return simulator.simulate_fork_join(
            KEY, 15.0, 3_000, slow, chunk_size=512,
            cluster=ClusterSpec(r=2, fault=fault))

    base = go(ALL_UP)  # same RNG plan as the hedged run, hedge never fires
    hedged = go(dataclasses.replace(ALL_UP, hedge_after_seconds=0.05))
    assert float(hedged.quantile(0.95)) <= float(base.quantile(0.95)) + 1e-6
    assert float(hedged.mean_response) <= float(base.mean_response) + 1e-6


# ------------------------------------------------------------ plan / sweep

def test_plan_survive_faults_is_conservative():
    """ACCEPTANCE: the N+k plan never provisions fewer replicas, and the
    simulated cross-check records the k-down p95."""
    kw = dict(simulate=True, key=KEY, n_queries=4_000)
    plan0 = capacity.plan_capacity(PARAMS, 120.0, 0.3, **kw)
    plan1 = capacity.plan_capacity(PARAMS, 120.0, 0.3, survive_faults=1,
                                   **kw)
    assert plan1.n_replicas >= plan0.n_replicas + 1
    assert plan1.survive_faults == 1
    assert plan1.response_faulted_p95_ms is not None
    assert plan0.survive_faults == 0
    assert plan0.response_faulted_p95_ms is None


@pytest.mark.parametrize("fault", [
    FaultSpec(mtbf_seconds=9.0),
    FaultSpec(outages=((1, 0.0, 5.0),), broker_timeout_seconds=0.1)],
    ids=["mtbf", "outages"])
def test_plan_rejects_double_injection(fault):
    """A spec that brings outages of its own would double-inject."""
    with pytest.raises(ValueError, match="outages"):
        capacity.plan_capacity(
            PARAMS, 50.0, 0.3, survive_faults=1,
            cluster=ClusterSpec(fault=fault))


TAIL = FaultSpec(broker_timeout_seconds=0.1, quorum_k=3,
                 hedge_after_seconds=0.12)
# server 0 of every replica 10% slower: the Eq 7 sizing cannot see it, so
# the N+1 fleet misses the SLO and the loop grows it (10 -> 12 here)
SLOW = FaultSpec(degraded=((0, 1.1),))


def _plan(rate, fault, k, **kw):
    return capacity.plan_capacity(
        PARAMS, rate, 0.3, simulate=True, key=KEY, n_queries=4_000,
        survive_faults=k, cluster=ClusterSpec(fault=fault), **kw)


def test_plan_accepts_quorum_hedge_spec():
    """A timeout, quorum and hedge run in both simulations; the faulted
    run's partial-quorum share is on the plan."""
    plan1 = _plan(120.0, TAIL, 1)
    plan0 = _plan(120.0, TAIL, 0)
    assert plan1.n_replicas >= plan0.n_replicas + 1
    assert 0.0 < plan1.faulted_degraded_fraction < 1.0
    assert plan1.response_faulted_p95_ms <= 300.0
    assert plan0.faulted_degraded_fraction is None
    assert plan0.response_faulted_p95_ms is None


def test_plan_simulated_numbers_describe_returned_fleet(events):
    """The loop grows the fleet past N+1, counting one faulted simulation
    for each fleet it tries (10 and 11 miss, 12 holds), and both simulated
    checks on the plan are runs of the fleet it returns: bit for bit a
    direct run at ``n_replicas``, with and without replica 0 down for the
    whole run, read as the planner reads one."""
    n_base = _plan(100.0, None, 0).n_replicas
    before = events["/repro/plan/faulted_sim"]
    plan = _plan(100.0, SLOW, 1)
    assert plan.n_replicas == n_base + 3
    assert events["/repro/plan/faulted_sim"] - before == 3
    whole = capacity._read(
        run(SLOW, r=plan.n_replicas, rate=100.0, chunk_size=4_096))
    down = capacity._read(
        run(dataclasses.replace(SLOW, outages=((0, 0.0, math.inf),)),
            r=plan.n_replicas, rate=100.0, chunk_size=4_096))
    assert plan.response_simulated_ms == float(whole["mean"]) * 1e3
    assert plan.response_simulated_p95_ms == float(whole["p95"]) * 1e3
    assert plan.response_faulted_p95_ms == float(down["p95"]) * 1e3
    assert plan.response_faulted_p95_ms <= 300.0
    assert plan.faulted_degraded_fraction == 0.0     # no timeout: whole


@pytest.mark.parametrize("need", [5, 6, 7, 12, 40, 100, 255, 256, 300])
def test_fleet_growth_finds_the_smallest_in_log_steps(need):
    """The N+k loop's search: from 5 replicas, the smallest fleet that
    holds (or the cap of 256 where none below it does), trying
    O(log n) fleets, each once."""
    tried = []

    def holds(n):
        tried.append(n)
        return n >= need

    n = capacity._grow(holds, 5, 256)
    assert n == min(need, 256)
    assert len(tried) == len(set(tried))
    assert len(tried) <= 2 * math.ceil(math.log2(256 - 5 + 1)) + 1


def test_plan_warns_when_the_cap_stops_growth(monkeypatch):
    """Where the faulted p95 cannot meet the SLO the loop stops at the
    simulation cap and says so."""
    n_base = _plan(100.0, None, 0).n_replicas
    monkeypatch.setattr(capacity, "_SIM_REPLICA_CAP", n_base + 2)
    with pytest.warns(UserWarning, match="cap"):
        plan = _plan(100.0, FaultSpec(degraded=((0, 1.5),)), 1)
    assert plan.n_replicas == n_base + 2
    assert plan.response_faulted_p95_ms > 300.0


def test_sweep_fault_axis_round_trips():
    from repro.core import sweep as sw
    faults = (None, FaultSpec(outages=((0, 0.0, 1e9),)))
    grid = sw.SweepGrid.build(lam=[40.0], p=[4.0], hit=[PARAMS.hit],
                              base=PARAMS, broker_from_p=False,
                              r=[3.0], fault=faults)
    assert grid.shape[-1] == 2
    res = sw.sweep_simulated(grid, KEY, n_queries=2_000, chunk_size=512)
    spill = np.ravel(np.asarray(res.stats.spill_fraction))
    assert spill[0] == 0.0 and spill[1] > 0.2
    with pytest.raises(ValueError, match="fault"):
        sw.sweep_analytical(grid)
    with pytest.raises(ValueError, match="6th axis|axis"):
        sw.SweepGrid.build(
            lam=[40.0], p=[4.0], hit=[0.5], base=PARAMS, r=[2.0],
            fault=faults,
            autoscale=(None,))


def test_faultspec_validation():
    with pytest.raises(ValueError):
        FaultSpec(outages=((0, 5.0, 5.0),))        # empty window
    with pytest.raises(ValueError):
        FaultSpec(outages=((-1, 0.0, 1.0),))       # bad index
    with pytest.raises(ValueError):
        FaultSpec(degraded=((0, 0.0),))            # factor must be > 0
    with pytest.raises(ValueError):
        FaultSpec(broker_timeout_seconds=0.0)
    with pytest.raises(ValueError):
        FaultSpec(quorum_k=0)
    with pytest.raises(ValueError):
        FaultSpec(hedge_backoff=0.5)
    with pytest.raises(TypeError):
        ClusterSpec(fault="down")                  # not a FaultSpec
    # quorum clips to the fork width
    assert FaultSpec(broker_timeout_seconds=1.0, quorum_k=9).quorum(4) == 4
    # hedge delays back off geometrically
    spec = FaultSpec(hedge_after_seconds=0.1, hedge_backoff=2.0,
                     hedge_attempts=3)
    np.testing.assert_allclose(spec.hedge_delays(), (0.1, 0.3, 0.7))


# ------------------------------------------------ hypothesis: carry chaining
# Guarded like tests/test_autoscale.py so the rest of the module runs
# without hypothesis.

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _N = 96
    _R = 4
    _SPEC = FaultSpec(outages=((0, 0.4, 1.1), (2, 2.0, 2.5)),
                      mtbf_seconds=1.5, mttr_seconds=0.4)
    _GAPS = jnp.asarray(
        np.random.default_rng(0).exponential(0.03, (2, _N)), jnp.float32)
    _T = jnp.cumsum(_GAPS, axis=1)
    _U = jnp.asarray(np.random.default_rng(1).random((2, _N, _R)),
                     jnp.float32)

    @given(st.lists(st.integers(min_value=1, max_value=_N - 1),
                    min_size=0, max_size=6, unique=True))
    @settings(max_examples=30, deadline=None)
    def test_fault_scan_chunking_invariant(cuts):
        """ACCEPTANCE: splitting the stream at ANY boundaries and
        chaining the carry reproduces the monolithic per-query replica
        masks exactly — the outage recurrence is chunking-invariant,
        which is what lets the streaming engine run it per chunk."""
        carry0 = fault_init(_SPEC, 2, _R)
        _, whole = fault_scan(_SPEC, _R, carry0, _T, _GAPS, _U)
        bounds = [0] + sorted(cuts) + [_N]
        carry = fault_init(_SPEC, 2, _R)
        parts = []
        for a, b in zip(bounds[:-1], bounds[1:]):
            carry, m = fault_scan(_SPEC, _R, carry, _T[:, a:b],
                                  _GAPS[:, a:b], _U[:, a:b])
            parts.append(np.asarray(m))
        np.testing.assert_array_equal(np.concatenate(parts, axis=1),
                                      np.asarray(whole))
else:
    @pytest.mark.skip(reason="property tests need hypothesis (see "
                      "pyproject [project.optional-dependencies].test)")
    def test_fault_scan_chunking_invariant():
        pass
