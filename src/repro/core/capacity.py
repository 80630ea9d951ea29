"""Capacity planning engine (paper Section 6).

Encodes the paper's measured parameter tables (Table 5 validation cluster,
Table 6 100-server case study with 1x..4x main memory) and the Scenario 1-6
what-if machinery: resource upgrades, SLO solving, replication sizing, and
the application-level result cache (Eq 8).

All sweeps evaluate as single XLA programs over (lambda-grid x scenario).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import queueing
from repro.core.cluster import ClusterSpec, resolve_cluster
from repro.core.faults import FaultSpec
from repro.core.queueing import ServerParams
from repro.launch.elastic import AutoscalePolicy
from repro.obs.spans import count, span

Array = jax.Array

__all__ = [
    "TABLE5_PARAMS",
    "MEMORY_TABLE",
    "broker_service_time",
    "scenario_params",
    "upper_bound_curve",
    "max_rate_under_slo",
    "replicas_needed",
    "CapacityPlan",
    "plan_capacity",
    "upgrade_grid",
]

_MS = 1e-3
# the largest fleet plan_capacity simulates
_SIM_REPLICA_CAP = 256

# --- Paper Table 5: validation cluster (8 servers, b = 1.25M pages) -------
TABLE5_PARAMS = ServerParams(
    p=8, s_broker=0.52 * _MS, s_hit=9.20 * _MS, s_miss=10.04 * _MS,
    s_disk=28.08 * _MS, hit=0.17)

TABLE5_SBROKER = {2: 0.33 * _MS, 4: 0.39 * _MS, 8: 0.52 * _MS}

# --- Paper Table 6: case-study parameters, p=100, b = 10M pages -----------
# Keyed by main-memory size as a multiple of the reference machine.
# (s_hit, s_miss, s_disk, hit)
MEMORY_TABLE = {
    1: (28.23 * _MS, 35.31 * _MS, 66.03 * _MS, 0.02),
    2: (33.38 * _MS, 33.77 * _MS, 35.89 * _MS, 0.09),
    3: (34.57 * _MS, 32.66 * _MS, 30.48 * _MS, 0.15),
    4: (34.68 * _MS, 32.04 * _MS, 26.14 * _MS, 0.18),
}


def broker_service_time(p) -> Array:
    """Paper's broker fit: S_broker = 3.18e-2 * p + 0.265  (milliseconds).

    R^2 = 0.99999 on the Table 5 measurements; gives 3.45 ms at p = 100.
    """
    p = jnp.asarray(p, jnp.float32)
    return (3.18e-2 * p + 0.265) * _MS


def scenario_params(
    *, memory: int = 1, cpu: float = 1.0, disk: float = 1.0, p: int = 100,
) -> ServerParams:
    """Build Section-6 scenario parameters.

    memory in {1,2,3,4} selects the re-measured Table 6 column; cpu/disk
    are speedup factors applied per the paper (divide CPU times by ``cpu``,
    disk time by ``disk``; the broker is CPU-bound so it scales with cpu).
    """
    s_hit, s_miss, s_disk, hit = MEMORY_TABLE[memory]
    return ServerParams(
        p=p,
        s_broker=broker_service_time(p) / cpu,
        s_hit=s_hit / cpu,
        s_miss=s_miss / cpu,
        s_disk=s_disk / disk,
        hit=hit,
    )


# Named paper scenarios (Section 6 / Figure 12).
def scenario(name: str, p: int = 100) -> ServerParams:
    table = {
        "baseline": dict(memory=1),
        "memory+disks": dict(memory=4, disk=4.0),
        "memory+cpus": dict(memory=4, cpu=4.0),
        "cpus+disks": dict(memory=1, cpu=4.0, disk=4.0),
        "memory+cpus+disks": dict(memory=4, cpu=4.0, disk=4.0),
    }
    return scenario_params(p=p, **table[name])


def upper_bound_curve(lam_grid: Array, params: ServerParams) -> Array:
    """Eq 7 upper bound over a lambda grid (one XLA program)."""
    _, hi = queueing.response_time_bounds(lam_grid, params)
    return hi


@functools.partial(jax.jit, static_argnames=("iters",))
def max_rate_under_slo(
    params: ServerParams,
    slo_seconds: float,
    *,
    result_cache: Optional[tuple[float, float]] = None,
    iters: int = 60,
) -> Array:
    """Largest lambda with upper-bound response time <= SLO (bisection).

    result_cache: optional (hit_result, s_broker_cache_hit) enabling Eq 8.
    R(lambda) is monotone increasing up to saturation, so bisection on
    [0, saturation_rate) is exact to float precision.

    One compiled program per input structure: the parameters, the SLO and
    the cache's values are traced, so calls that differ only in those
    values reuse it; ``result_cache=None`` and a pair are two programs.
    """
    lam_max = queueing.saturation_rate(params) * (1.0 - 1e-6)

    def response(lam):
        if result_cache is None:
            _, hi = queueing.response_time_bounds(lam, params)
            return hi
        hit_r, s_cache = result_cache
        return queueing.response_time_with_result_cache(
            lam, params, hit_r, s_cache)

    lo = jnp.asarray(0.0)
    hi = lam_max

    def body(state, _):
        count("plan/size_traced")    # runs when the sizing is traced
        lo, hi = state
        mid = 0.5 * (lo + hi)
        ok = response(mid) <= slo_seconds
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)), None

    (lo, hi), _ = jax.lax.scan(body, (lo, hi), None, length=iters)
    # infeasible SLO (even lambda->0 exceeds it) -> 0
    feasible = response(jnp.asarray(1e-6)) <= slo_seconds
    return jnp.where(feasible, lo, 0.0)


def replicas_needed(
    params: ServerParams,
    target_rate: float,
    slo_seconds: float,
    *,
    result_cache: Optional[tuple[float, float]] = None,
) -> tuple[Array, Array]:
    """Cluster replicas to serve target_rate within the SLO (Sec 6).

    Replication splits arrivals evenly; gains are linear per the paper.
    Returns (n_replicas, per_replica_rate).
    """
    per_replica = max_rate_under_slo(params, slo_seconds,
                                     result_cache=result_cache)
    n = jnp.ceil(jnp.asarray(target_rate) / jnp.maximum(per_replica, 1e-9))
    return n.astype(jnp.int32), per_replica


@dataclasses.dataclass(frozen=True)
class CapacityPlan:
    """Output of plan_capacity — the manager-facing answer (Sec 5, Q i-iii).

    ``response_simulated_ms``/``response_simulated_p95_ms`` are filled
    when the plan was cross-checked by the replicated streaming simulator
    (``plan_capacity(..., simulate=True)``): the planned topology —
    ``n_replicas`` dispatcher-routed copies of the p-server cluster,
    result cache included — run at the full target rate.

    ``autoscale``/``mean_active_replicas`` are filled when the cross
    check ran an elastic fleet (``cluster=ClusterSpec(autoscale=...)``):
    the policy that was simulated and the time-averaged active replica
    count it actually used — comparing it to ``n_replicas`` (the static
    Sec-6 answer, which stays the provisioning headline) quantifies the
    elastic saving.

    ``survive_faults``/``response_faulted_p95_ms``/
    ``faulted_degraded_fraction`` are the N+k survivability extension
    (``plan_capacity(..., survive_faults=k)``): the fleet is provisioned
    with k spare replicas so the SLO holds with k replicas down, and —
    when the simulated cross-check ran — ``response_faulted_p95_ms`` is
    the observed p95 of exactly that degraded scenario (k replicas held
    down for the whole run, round-robin failover splitting their share
    evenly over the survivors) and ``faulted_degraded_fraction`` the
    share of its queries answered on a partial quorum (the price of
    "good enough" answers; 0 without a broker timeout).  Both describe
    the returned ``n_replicas``, as the fault-free
    ``response_simulated_*`` do.
    """

    n_replicas: int
    servers_per_replica: int
    total_servers: int
    per_replica_rate_qps: float
    response_upper_ms: float
    response_lower_ms: float
    utilization: float
    response_simulated_ms: Optional[float] = None
    response_simulated_p95_ms: Optional[float] = None
    routing: Optional[str] = None
    autoscale: Optional[AutoscalePolicy] = None
    mean_active_replicas: Optional[float] = None
    survive_faults: int = 0
    response_faulted_p95_ms: Optional[float] = None
    faulted_degraded_fraction: Optional[float] = None


def plan_capacity(
    params: ServerParams,
    target_rate: float,
    slo_seconds: float,
    *,
    cluster: Optional[ClusterSpec] = None,
    result_cache: Optional[tuple[float, float]] = None,
    simulate: bool = False,
    key=None,
    routing: Optional[str] = None,
    n_queries: int = 60_000,
    mode: str = "exponential",
    survive_faults: int = 0,
) -> CapacityPlan:
    """Section-6 sizing, optionally cross-checked by simulation.

    The analytical path is unchanged: ``replicas_needed`` sizes the
    cluster off the Eq 7/Eq 8 upper bound.  ``simulate=True``
    additionally runs the replicated streaming simulator
    (`repro.core.simulator.simulate_fork_join` with ``r=n_replicas`` and
    the same result cache) at the FULL target rate, so the plan's
    headline numbers carry a mechanistic sanity check of the even-split
    assumption under an actual routing policy.

    ``cluster=ClusterSpec(...)`` supplies the topology knobs (routing,
    result cache, replica engine, autoscale policy); its ``r`` must stay
    at the default — sizing the fleet is this function's job.  The loose
    ``routing=`` / ``result_cache=`` keywords keep working through the
    `repro.core.cluster.resolve_cluster` deprecation shim.

    With ``autoscale=AutoscalePolicy(...)`` on the spec the simulated
    cross-check runs THAT elastic fleet instead of ``n_replicas`` static
    copies (the policy's ``max_r`` sets provisioning), and the plan
    reports the policy plus its time-averaged ``mean_active_replicas``
    — the replica-seconds integral that makes "elastic vs static" a
    like-for-like cost comparison.  Policies need the simulator, so
    ``simulate=False`` with an autoscale policy is an error.

    ``survive_faults=k`` is the N+k survivability criterion (the
    ROADMAP's "one replica down at global peak" question, k=1): the
    fleet is sized so the SLO still holds with k replicas down — the
    Eq 7/8 bound is evaluated at the SURVIVOR rate ``target_rate /
    (n - k)`` and ``n`` gains k spares, so the plan is always at least
    as conservative as the fault-free one (equal at k=0).  With
    ``simulate=True`` the cross-check runs exactly that degraded
    scenario — replicas 0..k-1 held down for the whole run via
    `repro.core.faults.FaultSpec` outage windows, failover moving their
    share to the survivors — and if the observed p95 still misses the
    SLO (imbalance or tails the even-split bound can't see), the fleet
    is grown by 1, 2, 4, ... replicas until it holds and then bisected
    down to the smallest fleet that holds (the p95 falls as the fleet
    grows), or stops at the simulator's 256-replica cap, which warns
    (``response_faulted_p95_ms``).  A ``ClusterSpec.fault`` with no
    outages of its own (a broker timeout and quorum, hedging, degraded
    servers) is run by both simulations, the outages added to it; one
    with ``outages`` or ``mtbf_seconds`` is refused.  The fault-free
    cross-check runs after the fleet is settled, so every simulated
    number of the plan describes the returned ``n_replicas``.
    """
    spec = resolve_cluster(cluster, routing=routing,
                           result_cache=result_cache,
                           caller="plan_capacity")
    if spec.r != 1:
        raise ValueError(
            "plan_capacity sizes the fleet itself; leave ClusterSpec.r "
            "at its default")
    if spec.autoscale is not None and not simulate:
        raise ValueError(
            "an autoscale policy only affects the simulated cross-check "
            "(the Eq 7/8 sizing is static); pass simulate=True")
    k_down = int(survive_faults)
    if k_down < 0:
        raise ValueError(f"survive_faults must be >= 0; got {survive_faults}")
    if k_down and spec.autoscale is not None:
        raise ValueError(
            "survive_faults sizes a static fleet; with an autoscale "
            "policy the max_r provisioning is the policy's job — plan "
            "the two separately")
    if k_down and spec.fault is not None and spec.fault.has_outages:
        raise ValueError(
            "survive_faults adds its own k-replicas-down outages to the "
            "ClusterSpec.fault; a fault spec with outages or an MTBF "
            "process would double-inject — give one or the other")
    with span("plan"):
        return _plan(params, target_rate, slo_seconds, spec, simulate,
                     key, n_queries, mode, k_down)


@jax.jit
def _size(params, target_rate, slo_seconds, cache):
    """The Section 6 sizing as one program: the fault-free replica count,
    the per-replica rate, and at the survivor rate ``target / max(n, 1)``
    the Eq 7 bounds (Eq 8 upper with a cache) and the utilization."""
    n, per_replica = replicas_needed(params, target_rate, slo_seconds,
                                     result_cache=cache)
    rate = target_rate / jnp.maximum(n, 1)
    lo, hi = queueing.response_time_bounds(rate, params)
    if cache is not None:
        hi = queueing.response_time_with_result_cache(rate, params, *cache)
    util = queueing.utilization(rate, queueing.service_time_server(params))
    return n, per_replica, lo, hi, util


@jax.jit
def _read(sim):
    """The numbers a plan reads off a simulation, as one program: its mean
    and 95th percentile, and where the run had them the share answered on
    a partial quorum and the mean active replica count."""
    out = {"mean": sim.mean_response, "p95": sim.quantile(0.95)}
    if sim.degraded_count is not None:
        out["degraded"] = sim.degraded_fraction
    if sim.replica_seconds is not None:
        out["mean_active"] = sim.mean_active_replicas
    return out


def _grow(holds, n0: int, cap: int) -> int:
    """The smallest fleet from ``n0`` up for which ``holds(n)``, taken to
    stay true as n grows: fleets n0, n0 + 1, n0 + 2, n0 + 4, ... until one
    holds, then a bisection between the last that did not and it, so
    O(log n) calls of ``holds``.  Where no fleet below ``cap`` holds, cap,
    whether or not it holds."""
    if holds(n0):
        return n0
    lo, step = n0, 1
    while lo < cap:
        hi = min(n0 + step, cap)
        if holds(hi):
            while hi - lo > 1:
                mid = (lo + hi) // 2
                if holds(mid):
                    hi = mid
                else:
                    lo = mid
            return hi
        lo, step = hi, 2 * step
    return lo


def _survive(key, params, target_rate, slo_seconds, spec, n_queries, mode,
             k_down, n_i):
    """The N+k check: replicas 0..k-1 down for the whole run (the
    peak-coincident worst case) on top of the spec's own timeout, quorum,
    hedging and degraded servers.  The even-split bound already sized for
    this; the simulation also sees the tails it cannot, so where the
    faulted p95 misses the SLO the fleet grows (`_grow`, one faulted
    simulation per fleet size it tries) up to the simulation cap, which
    warns.  Returns ``(n, numbers)``, the numbers of ``_read`` of the
    faulted run at n."""
    from repro.core import simulator  # deferred: planner-only dep
    down = dataclasses.replace(
        spec.fault or FaultSpec(),
        outages=tuple((j, 0.0, math.inf) for j in range(k_down)))
    runs = {}

    def holds(n):
        ft = simulator.simulate_fork_join(
            key, float(target_rate), n_queries, params, mode=mode,
            cluster=dataclasses.replace(spec, r=n, fault=down))
        count("plan/faulted_sim")
        with span("plan.faults.read"):
            runs[n] = jax.device_get(_read(ft))
        return float(runs[n]["p95"]) <= slo_seconds

    n = _grow(holds, n_i, _SIM_REPLICA_CAP)
    if float(runs[n]["p95"]) > slo_seconds:
        warnings.warn(
            f"the N+{k_down} fleet stopped growing at the "
            f"{_SIM_REPLICA_CAP}-replica simulation cap with the faulted "
            f"p95 at {float(runs[n]['p95']) * 1e3:.1f} ms, above the "
            f"{slo_seconds * 1e3:g} ms SLO", UserWarning, stacklevel=4)
    return n, runs[n]


def _plan(params, target_rate, slo_seconds, spec, simulate, key,
          n_queries, mode, k_down) -> CapacityPlan:
    """`plan_capacity` after its arguments are checked: the sizing, then
    the simulated cross-check, each under its own span."""
    cache = spec.result_cache
    with span("plan.size"):
        n, per_replica, lo, hi, util = jax.device_get(_size(
            params, float(target_rate), float(slo_seconds), cache))
        # N+k: the bound must hold at the SURVIVOR rate target / n_base,
        # so provisioning gains k spares on top of the fault-free answer
        n_i = int(n) + k_down
        rate = float(target_rate) / max(int(n), 1)
        p = int(params.p)
        upper_ms, lower_ms = float(hi) * 1e3, float(lo) * 1e3
        util = float(util)
        per_replica = float(per_replica)
    sim_ms = sim_p95_ms = mean_active = faulted_p95_ms = degraded = None
    sim_r = (spec.autoscale.max_r if spec.autoscale is not None else n_i)
    feasible = per_replica > 1e-9 or spec.autoscale is not None
    if simulate and feasible and sim_r <= _SIM_REPLICA_CAP:
        from repro.core import simulator  # deferred: planner-only dep
        key = jax.random.PRNGKey(0) if key is None else key
        if k_down:
            with span("plan.faults"):
                n_i, faulted = _survive(
                    key, params, target_rate, slo_seconds, spec, n_queries,
                    mode, k_down, n_i)
            faulted_p95_ms = float(faulted["p95"]) * 1e3
            degraded = float(faulted["degraded"])
        sim_spec = (spec if spec.autoscale is not None
                    else dataclasses.replace(spec, r=n_i))
        with span("plan.simulate"):
            sim = simulator.simulate_fork_join(
                key, float(target_rate), n_queries, params, mode=mode,
                cluster=sim_spec)
        with span("plan.read"):
            got = jax.device_get(_read(sim))
            sim_ms = float(got["mean"]) * 1e3
            sim_p95_ms = float(got["p95"]) * 1e3
            if spec.autoscale is not None:
                mean_active = float(got["mean_active"])
    elif simulate:
        reason = ("infeasible SLO" if per_replica <= 1e-9
                  else f"above the {_SIM_REPLICA_CAP}-replica simulation "
                       "cap")
        warnings.warn(
            f"skipping the simulated cross-check: the plan needs {sim_r} "
            f"replicas ({reason}); run simulate_fork_join directly with "
            "a smaller chunk_size if you really want this",
            UserWarning, stacklevel=3)
    return CapacityPlan(
        n_replicas=n_i,
        servers_per_replica=p,
        total_servers=n_i * p,
        per_replica_rate_qps=rate,
        response_upper_ms=upper_ms,
        response_lower_ms=lower_ms,
        utilization=util,
        response_simulated_ms=sim_ms,
        response_simulated_p95_ms=sim_p95_ms,
        routing=spec.routing if sim_ms is not None else None,
        autoscale=spec.autoscale if sim_ms is not None else None,
        mean_active_replicas=mean_active,
        survive_faults=k_down,
        response_faulted_p95_ms=faulted_p95_ms,
        faulted_degraded_fraction=degraded,
    )


def upgrade_grid(
    lam: float,
    *,
    memory: int = 1,
    cpu_speeds: Array = None,
    disk_speeds: Array = None,
    p: int = 100,
    result_cache: Optional[tuple[float, float]] = None,
) -> Array:
    """Fig 13/14 surface: upper-bound R over (cpu_speed x disk_speed)."""
    cpu_speeds = jnp.asarray(
        cpu_speeds if cpu_speeds is not None else jnp.linspace(1, 4, 7))
    disk_speeds = jnp.asarray(
        disk_speeds if disk_speeds is not None else jnp.linspace(1, 4, 7))
    s_hit, s_miss, s_disk, hit = MEMORY_TABLE[memory]
    cs = cpu_speeds[:, None]
    ds = disk_speeds[None, :]
    params = ServerParams(
        p=p,
        s_broker=broker_service_time(p) / cs,
        s_hit=s_hit / cs,
        s_miss=s_miss / cs,
        s_disk=s_disk / ds,
        hit=hit,
    )
    if result_cache is None:
        _, hi = queueing.response_time_bounds(lam, params)
        return hi
    return queueing.response_time_with_result_cache(lam, params, *result_cache)
