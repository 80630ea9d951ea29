"""Section-6 case-study reproduction: the paper's own numbers."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import capacity, queueing
from repro.core.cluster import ClusterSpec

S6_CACHE = (0.5, 0.069e-3)
SCENARIOS = ("baseline", "memory+disks", "memory+cpus", "cpus+disks",
             "memory+cpus+disks")


def test_broker_fit_345ms_at_p100():
    """Paper: S_broker = 3.45 ms for p = 100."""
    assert np.isclose(float(capacity.broker_service_time(100)) * 1e3, 3.45,
                      atol=0.02)


def test_scenario4_286ms_at_56qps():
    """Paper Scenario 4: upper bound 286 ms at 56 queries/second."""
    p4 = capacity.scenario("memory+cpus+disks")
    _, hi = queueing.response_time_bounds(56.0, p4)
    assert abs(float(hi) * 1e3 - 286.0) < 3.0


def test_scenario4_replication_4x100_for_200qps():
    """Paper: 4 replicas x 100 servers serve 200 qps within 300 ms."""
    p4 = capacity.scenario("memory+cpus+disks")
    plan = capacity.plan_capacity(p4, 200.0, 0.300)
    assert plan.n_replicas == 4
    assert plan.total_servers == 400
    assert plan.response_upper_ms < 300.0


def test_scenario6_result_cache_282ms_at_65qps():
    """Paper Scenario 6: with result caching, 65 qps at ~282 ms."""
    p4 = capacity.scenario("memory+cpus+disks")
    r = queueing.response_time_with_result_cache(65.0, p4, 0.5, 0.069e-3)
    assert abs(float(r) * 1e3 - 282.0) < 5.0
    # and 3 replicas support the paper's 195 qps (3 x 65)
    n, per = capacity.replicas_needed(p4, 195.0, 0.300,
                                      result_cache=(0.5, 0.069e-3))
    assert int(n) == 3


def test_scenario_ordering_matches_paper():
    """Fig 12: memory+disks < memory+cpus < cpus+disks < all three
    (in max sustainable rate under the 300 ms SLO)."""
    names = ["baseline", "memory+disks", "memory+cpus", "cpus+disks",
             "memory+cpus+disks"]
    rates = [float(capacity.max_rate_under_slo(capacity.scenario(n), 0.300))
             for n in names]
    assert rates[0] < 1e-3                       # baseline infeasible
    assert rates[1] < rates[2] < rates[3] < rates[4]


def test_memory_scaling_table6():
    """Paper Scenario 1: 4x memory -> hit x9, disk demand / 2.53."""
    ref = capacity.MEMORY_TABLE[1]
    mem4 = capacity.MEMORY_TABLE[4]
    assert np.isclose(mem4[3] / ref[3], 9.0, rtol=0.01)
    assert np.isclose(ref[2] / (mem4[2] / 1.0), 66.03 / 26.14, rtol=0.01)


def test_upgrade_grid_shape_and_monotonicity():
    grid = capacity.upgrade_grid(4.0, memory=1)
    g = np.asarray(grid)
    assert g.shape == (7, 7)
    assert (np.diff(g, axis=0) <= 1e-9).all()  # faster cpu -> lower R
    assert (np.diff(g, axis=1) <= 1e-9).all()  # faster disk -> lower R


def test_fig13_crossover_memory_flips_bottleneck():
    """Fig 13: at 1x memory disk speed dominates; at 4x memory CPU does."""
    lam = 4.0
    g1 = np.asarray(capacity.upgrade_grid(lam, memory=1))
    g4 = np.asarray(capacity.upgrade_grid(lam, memory=4))
    disk_gain_1 = g1[0, 0] - g1[0, -1]   # vary disk at slow cpu
    cpu_gain_1 = g1[0, 0] - g1[-1, 0]
    disk_gain_4 = g4[0, 0] - g4[0, -1]
    cpu_gain_4 = g4[0, 0] - g4[-1, 0]
    assert disk_gain_1 > cpu_gain_1      # 1x memory: disk-bound
    assert cpu_gain_4 > disk_gain_4      # 4x memory: cpu-bound


def test_slo_solver_is_exact_boundary():
    p4 = capacity.scenario("memory+cpus+disks")
    lam = capacity.max_rate_under_slo(p4, 0.300)
    _, at = queueing.response_time_bounds(float(lam), p4)
    _, above = queueing.response_time_bounds(float(lam) * 1.02, p4)
    assert float(at) <= 0.300 + 1e-5
    assert float(above) > 0.300


def test_sizing_traced_at_most_once_per_structure(events):
    """The sizing is one program per input structure: calls that differ
    in rate and SLO reuse it, and a result cache makes a second one."""
    p4 = capacity.scenario("memory+cpus+disks")
    for cache in (None, S6_CACHE):
        before = events["/repro/plan/size_traced"]
        for rate, slo in ((150.0, 0.3), (200.0, 0.25), (275.0, 0.4)):
            capacity.plan_capacity(p4, rate, slo,
                                   cluster=ClusterSpec(result_cache=cache))
        assert events["/repro/plan/size_traced"] - before <= 1


def test_survive_faults_traces_engine_once_for_two_rates(events):
    """The N+k outage lasts the whole run whatever the rate, so two rates
    that size the same fleet reuse both simulations' programs."""
    from repro.core.faults import FaultSpec
    p4 = capacity.scenario("memory+cpus+disks")
    spec = ClusterSpec(fault=FaultSpec(broker_timeout_seconds=0.15,
                                       quorum_k=95,
                                       hedge_after_seconds=0.21))
    plans = []
    for rate in (150.0, 140.0):
        before = events["/repro/stream/traced"]
        plans.append(capacity.plan_capacity(
            p4, rate, 0.3, cluster=spec, simulate=True,
            key=jax.random.PRNGKey(3), n_queries=2_000, survive_faults=1))
        traced = events["/repro/stream/traced"] - before
    assert plans[0].n_replicas == plans[1].n_replicas == 4
    assert traced == 0


def test_max_rate_under_slo_composes_under_jit_and_vmap():
    p4 = capacity.scenario("memory+cpus+disks")
    slos = jnp.asarray([0.25, 0.3, 0.4])
    one = [float(capacity.max_rate_under_slo(p4, float(s))) for s in slos]
    mapped = jax.vmap(lambda s: capacity.max_rate_under_slo(p4, s))(slos)
    np.testing.assert_allclose(np.asarray(mapped), one, rtol=1e-6)
    cached = jax.jit(lambda prm, s: capacity.max_rate_under_slo(
        prm, s, result_cache=S6_CACHE))(p4, 0.3)
    assert np.isclose(float(cached), float(capacity.max_rate_under_slo(
        p4, 0.3, result_cache=S6_CACHE)), rtol=1e-6)


def _float64_sizing(prm, slo, cache):
    """Section 6 sizing in float64: the Eq 7/8 (lower, upper) bounds as a
    function of the rate, the server's service time, and the 60-step
    bisection's largest rate whose upper bound meets the SLO (0 where
    none does)."""
    s = prm.hit * prm.s_hit + (1 - prm.hit) * (prm.s_miss + prm.s_disk)
    sb = float(prm.s_broker)
    hp = sum(1.0 / i for i in range(1, int(prm.p) + 1))

    def mm1(lam, st):
        return st / (1 - lam * st) if lam * st < 1 else math.inf

    def bounds(lam):
        lo = mm1(lam, s) + mm1(lam, sb)
        hi = hp * mm1(lam, s) + mm1(lam, sb)
        if cache is not None:
            hit_r, s_cache = cache
            hi = hi * (1 - hit_r) + mm1(lam, s_cache) * hit_r
        return lo, hi

    a, b = 0.0, min(1 / s, 1 / sb) * (1 - 1e-6)
    for _ in range(60):
        mid = 0.5 * (a + b)
        a, b = (mid, b) if bounds(mid)[1] <= slo else (a, mid)
    return bounds, s, (a if bounds(1e-6)[1] <= slo else 0.0)


@pytest.mark.parametrize("k", [0, 1])
@pytest.mark.parametrize("cache", [None, S6_CACHE], ids=["eq7", "eq8"])
@pytest.mark.parametrize("name", SCENARIOS)
def test_sizing_matches_float64_bisection(name, cache, k):
    """The compiled sizing answers as a float64 bisection does.

    float32 resolves the upper bound to about 1e-6 of itself, which is a
    band of rates as wide as the bound is flat where it crosses the SLO:
    the replica count may take any value that band gives (one value, but
    where the bound at rate 0 is within a hair of the SLO).  The rest is
    compared at the plan's own survivor rate.  An infeasible SLO keeps
    the replica count that float32 saturates to."""
    prm = capacity.scenario(name)
    for slo in (0.001, 0.2, 0.3, 0.5):
        bounds, s, per = _float64_sizing(prm, slo, cache)
        for rate in (50.0, 123.0, 200.0, 300.0):
            plan = capacity.plan_capacity(
                prm, rate, slo, cluster=ClusterSpec(result_cache=cache),
                survive_faults=k)
            if per == 0.0:                     # no rate meets the SLO
                assert plan.n_replicas == 2**31 - 1 + k
                continue
            flat = slo * 1e-3 / (bounds(per * 1.001)[1] - slo)
            band = 1e-6 * max(flat, 1.0)
            n = plan.n_replicas - k
            assert (math.ceil(rate / (per * (1 + band))) <= n
                    <= math.ceil(rate / (per * (1 - band)))), (slo, rate)
            survivor = rate / n
            lo, hi = bounds(survivor)
            np.testing.assert_allclose(
                [plan.per_replica_rate_qps, plan.response_lower_ms,
                 plan.response_upper_ms, plan.utilization],
                [survivor, lo * 1e3, hi * 1e3, survivor * s], rtol=1e-5,
                err_msg=str((slo, rate)))
