"""Capacity planning for model serving — the paper's methodology applied to
the assigned architectures.

The paper's pipeline is: measure a single server -> parameterize Eq 1 ->
predict cluster response time under Poisson load -> size replication
(Section 6).  Here the "single-server measurement" is the compiled dry-run:
`cost_analysis()` FLOPs/bytes and the HLO collective bytes give a roofline
service-time estimate per step, which becomes S_server in the same
fork-join queueing model:

  * a TP/EP-sharded model step is a fork-join across shards (the join is
    the output collective), so shard-time imbalance pays the H_p tax just
    like index servers with heterogeneous disk caches;
  * replicas of the serving cell take the role of cluster replicas.

This closes the loop between the dry-run roofline (repro.roofline) and the
paper's planner: one can ask "how many serving cells does qwen3-8b
decode_32k need for 500 req/s under a 100 ms SLO?" and get the Section-6
style answer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import capacity, queueing, sweep
from repro.obs.spans import span

__all__ = ["HardwareSpec", "TPU_V5E", "RooflineTerms", "ServingModel",
           "serving_params", "plan_serving", "plan_over_grid"]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip hardware constants (defaults: TPU v5e, bf16)."""

    name: str
    peak_flops: float        # FLOP/s per chip
    hbm_bandwidth: float     # bytes/s per chip
    ici_bandwidth: float     # bytes/s per link
    vmem_bytes: float = 128 * 2**20
    hbm_bytes: float = 16 * 2**30


TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,
)


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """The three §Roofline terms, in seconds (already divided by chips)."""

    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound(self) -> float:
        """Perfect-overlap bound: all three engines run concurrently."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def step_time_serial_bound(self) -> float:
        """No-overlap (conservative, capacity-planning) bound."""
        return self.compute_s + self.memory_s + self.collective_s


def terms_from_analysis(
    *,
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    n_chips: int,
    hw: HardwareSpec = TPU_V5E,
) -> RooflineTerms:
    """§Roofline: aggregate HLO counters -> per-(arch, mesh) terms."""
    return RooflineTerms(
        compute_s=hlo_flops / (n_chips * hw.peak_flops),
        memory_s=hlo_bytes / (n_chips * hw.hbm_bandwidth),
        collective_s=collective_bytes / (n_chips * hw.ici_bandwidth),
    )


@dataclasses.dataclass(frozen=True)
class ServingModel:
    """A serving cell: one model replica sharded over n_chips."""

    name: str
    terms: RooflineTerms
    n_chips: int
    batch_per_step: int      # requests retired per step
    dispatch_overhead_s: float = 50e-6   # broker analogue


def serving_params(model: ServingModel, *,
                   overlap_fraction: float = 0.0,
                   straggler_jitter: float = 0.0) -> queueing.ServerParams:
    """Map a serving cell onto Eq 1 parameters.

    The compiled step is a synchronous pipeline over n_chips — its chip-
    level fork-join is already serialized inside the step time, so the
    queueing-level server is the CELL (p=1).  Eq 1's decomposition maps
    onto overlap: the "hit" path is a perfectly overlapped step (all three
    engines concurrent), the "miss" path is the serial bound, with
    ``overlap_fraction`` playing the disk-cache hit ratio.  Stochastic
    per-chip jitter (the paper's imbalance) enters as an H_p-scaled
    inflation of the collective (join) term via ``straggler_jitter`` in
    [0, 1]: 0 = deterministic chips, 1 = fully exponential shard times.
    """
    t = model.terms
    jitter_tax = 1.0 + straggler_jitter * (
        float(queueing.harmonic_number(model.n_chips)) - 1.0)
    return queueing.ServerParams(
        p=1,
        s_broker=model.dispatch_overhead_s,
        s_hit=t.step_time_lower_bound,
        s_miss=t.compute_s + t.memory_s,
        s_disk=t.collective_s * jitter_tax,
        hit=overlap_fraction,
    )


@dataclasses.dataclass(frozen=True)
class ServingPlan:
    model: str
    cells: int
    chips: int
    per_cell_rate: float
    response_upper_ms: float
    utilization: float
    bound: str


def plan_serving(
    model: ServingModel,
    target_rate_per_s: float,
    slo_seconds: float,
    *,
    result_cache: Optional[tuple[float, float]] = None,
) -> ServingPlan:
    """Section-6 case study for a model serving fleet.

    target_rate is in *requests*/s; a step retires batch_per_step requests,
    so the step arrival rate is rate / batch_per_step (continuous-batching
    approximation).
    """
    params = serving_params(model)
    step_rate_slo = capacity.max_rate_under_slo(
        params, slo_seconds, result_cache=result_cache)
    per_cell_req_rate = float(step_rate_slo) * model.batch_per_step
    if per_cell_req_rate <= 1e-6:
        # SLO below the single-step service time: no fleet size helps —
        # the latency floor is a property of the cell, not of replication
        # (the paper's baseline scenario: infeasible "even at very low
        # query arrival rates").
        return ServingPlan(
            model=model.name, cells=0, chips=0, per_cell_rate=0.0,
            response_upper_ms=float("inf"), utilization=0.0,
            bound=model.terms.bound)
    cells = max(1, math.ceil(target_rate_per_s / per_cell_req_rate))
    rate = target_rate_per_s / cells / model.batch_per_step
    if result_cache is None:
        _, hi = queueing.response_time_bounds(rate, params)
    else:
        hi = queueing.response_time_with_result_cache(
            rate, params, *result_cache)
    util = queueing.utilization(rate, queueing.service_time_server(params))
    return ServingPlan(
        model=model.name,
        cells=cells,
        chips=cells * model.n_chips,
        per_cell_rate=per_cell_req_rate,
        response_upper_ms=float(hi) * 1e3,
        utilization=float(util),
        bound=model.terms.bound,
    )


def plan_over_grid(
    grid: sweep.SweepGrid,
    slo_seconds: float,
    *,
    cost_fn: Optional[Callable] = None,
    simulate: bool = False,
    key=None,
    quantile: Optional[float] = None,
    n_queries: Optional[int] = None,
    profile=None,
    profile_bin_seconds: float = 3600.0,
    mesh=None,
    **sim_kwargs,
):
    """Section-6 what-if analysis over a whole configuration grid at once.

    Default: evaluates the analytical (Eq 7 upper bound) response surface
    for every (lambda, p, cpu, disk, hit) combination as one XLA program
    and extracts the constraint-satisfying frontier: per arrival rate, the
    cheapest configuration with R_upper <= SLO.  Returns the dense surface
    too so callers can plot Figs 9-12 style curves from the same
    evaluation.

    New knobs opened by the streaming simulation core:

      * ``simulate=True`` — replace the analytic surface with the
        streaming-simulated one (`sweep.sweep_simulated`); ``n_queries``
        and any extra ``sim_kwargs`` (mode, impl, chunk_size, hist_bins)
        pass through, and memory stays bounded by the chunk size no matter
        how long the simulated horizon is.
      * ``quantile=0.95`` — plan against tail latency instead of the
        mean/upper surface (works for both analytic and simulated paths).
      * ``profile=`` a relative-rate curve (e.g. ``loadgen.diurnal_rates``)
        with ``profile_bin_seconds`` — makes every simulated scenario's
        load time-varying, so "the cheapest config whose p95 survives the
        daily peak" is ``simulate=True, quantile=0.95, profile=...``.

    Replication rides the grid itself: build it with ``r=[1, 2, 4]``
    (and optionally ``result_cache=(hit_r, s_cache)``) and both paths
    price r dispatcher-routed replicas per cell — analytically at
    ``lam / r`` via Eq 7/8, simulated under a real routing policy
    (``cluster=ClusterSpec(routing="jsq")`` etc. passes through
    ``sim_kwargs``).  The frontier then answers "replicate, upgrade, or
    cache?" in one extraction.

    Elastic fleets ride the grid the same way: build it with
    ``autoscale=(AutoscalePolicy(...), ...)`` — the replica axis becomes
    a POLICY axis — and with ``simulate=True`` the frontier prices each
    policy by its observed replica-seconds, answering "which autoscaler
    config is cheapest under the p95 SLO over this load profile".
    Policy grids are simulation-only; the analytic path raises.

    ``mesh`` (a 1-D mesh from `repro.launch.mesh.make_sweep_mesh`) shards
    the scenario axis of either surface across devices — the
    million-scenario planning path of ``examples/global_sweep.py``.
    """
    if not simulate and (profile is not None or key is not None
                         or n_queries is not None or sim_kwargs):
        raise ValueError(
            "profile/key/n_queries/simulation kwargs only take effect "
            "with simulate=True; the analytic path would silently "
            "ignore them")
    with span("grid"):
        if simulate:
            key = jax.random.PRNGKey(0) if key is None else key
            result = sweep.sweep_simulated(
                grid, key,
                n_queries=20_000 if n_queries is None else n_queries,
                profile=profile, profile_bin_seconds=profile_bin_seconds,
                mesh=mesh, **sim_kwargs)
        else:
            result = sweep.sweep_analytical(grid, mesh=mesh)
        with span("frontier"):
            frontier = sweep.extract_frontier(
                result, slo_seconds, cost_fn=cost_fn, quantile=quantile)
    return result, frontier
