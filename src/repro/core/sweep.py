"""Vectorized what-if sweep engine (paper Sec 6 at grid scale).

The paper answers "will configuration X keep response time under the
constraint?" one scenario at a time.  This module evaluates a dense
Cartesian grid

    lambda x p x cpu-speedup x disk-speedup x cache-hit-ratio x replicas

(the replica axis optionally swapped for a tuple of elastic
`AutoscalePolicy` values — a POLICY axis, simulation-only) as a SINGLE
XLA program, two ways:

  * analytical — the Eq 7 bounds from `repro.core.queueing`, which already
    broadcast, evaluated over the broadcasted grid.  Tens of thousands of
    scenarios cost one fused elementwise kernel.
  * simulation — the STREAMING chunked engine of `repro.core.simulator`:
    per distinct p, all L*C*D*H scenarios' sample paths run as one
    `lax.scan` over query chunks (optionally on the `maxplus_scan` Pallas
    grid), carrying only per-(scenario, server) max-plus state plus
    streaming statistics.  Peak memory is scenarios x p x chunk floats —
    independent of n_queries — so grids 10-100x larger than the old
    materializing path fit, quantile surfaces (p95/p99) come out next to
    the means, and an `ArrivalProcess` profile makes every scenario's
    load time-varying (diurnal/weekly peaks).

On top sits constraint-satisfying frontier extraction: "for each arrival
rate, the cheapest configuration with R <= SLO", where R can be the
analytic upper bound, the simulated mean, or a simulated quantile such as
p95 (exposed to planners via `repro.core.planner.plan_over_grid`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro import compat
from repro.core import capacity, queueing, simulator
from repro.core.arrivals import ArrivalProcess
from repro.core.cluster import ClusterSpec, resolve_cluster
from repro.core.faults import FaultSpec
from repro.core.queueing import ServerParams
from repro.launch.elastic import AutoscalePolicy
from repro.obs.spans import span

Array = jax.Array
ArrayLike = Union[Array, Sequence[float], float]

__all__ = [
    "SweepGrid",
    "SweepResult",
    "SimSweepResult",
    "Frontier",
    "sweep_analytical",
    "sweep_simulated",
    "default_config_cost",
    "extract_frontier",
]

def _axis(x: ArrayLike) -> Array:
    return jnp.atleast_1d(jnp.asarray(x, jnp.float32))


@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """A dense what-if grid over the paper's Section-6 knobs.

    Axis order is fixed: (lam, p, cpu, disk, hit, r).  ``base`` supplies
    the measured per-server times that the cpu/disk speedups divide
    (paper convention: CPU k-times faster divides every CPU time by k);
    its ``p``/``hit`` fields are ignored in favor of the grid axes.  The
    broker is CPU-bound and grows with p per the paper's linear fit,
    unless ``broker_from_p=False`` pins it to ``base.s_broker``.

    ``r`` is the replica axis (Sec 6 ``replicas_needed`` as a grid
    dimension): ``lam`` stays the TOTAL arrival rate and each replica is
    planned at ``lam / r``.  ``result_cache=(hit_r, s_cache)`` threads
    the Eq 8 broker-level result cache through both evaluation paths
    (conservative un-thinned mixture analytically; a mechanistic
    dispatcher cache queue in the simulator).

    ``autoscale`` replaces the replica axis with a POLICY axis: a tuple
    of `repro.launch.elastic.AutoscalePolicy` values becomes the grid's
    6th dimension (``r`` must stay at its default — each policy's
    ``max_r`` sets provisioning).  Policy grids are simulation-only
    (the Eq 7/8 bounds have no notion of a time-varying fleet), and
    :func:`extract_frontier` prices their cells by observed
    replica-seconds instead of a static replica count.

    ``fault`` likewise replaces the replica axis with a FAULT-SCENARIO
    axis: a tuple of `repro.core.faults.FaultSpec` values (None entries
    are the fault-free baseline) becomes the 6th dimension, every cell
    running at the single fixed replica count on the ``r`` axis.  Fault
    grids are simulation-only too — the analytic bounds assume every
    replica is up — and answer "same hardware, which failure scenarios
    still meet the SLO?" in one dispatch sweep.
    """

    lam: Array
    p: Array
    cpu: Array
    disk: Array
    hit: Array
    base: ServerParams
    broker_from_p: bool = True
    r: Array = dataclasses.field(
        default_factory=lambda: jnp.ones((1,), jnp.float32))
    result_cache: Optional[tuple[float, float]] = None
    autoscale: Optional[tuple[AutoscalePolicy, ...]] = None
    fault: Optional[tuple[Optional[FaultSpec], ...]] = None

    def __post_init__(self):
        if self.fault is not None:
            fts = (tuple(self.fault)
                   if isinstance(self.fault, (tuple, list))
                   else (self.fault,))
            if not fts:
                raise ValueError("fault= needs at least one scenario "
                                 "(or None for a fault-free grid)")
            for ft in fts:
                if ft is not None and not isinstance(ft, FaultSpec):
                    raise TypeError(
                        "fault must hold FaultSpec (or None) values; "
                        f"got {type(ft).__name__}")
            if self.autoscale is not None:
                raise ValueError(
                    "autoscale and fault both claim the grid's 6th "
                    "axis; sweep one at a time")
            if self.r.shape[0] != 1:
                raise ValueError(
                    "a fault grid replaces the replica axis; give r ONE "
                    "value (the fixed replica count every scenario "
                    "runs at)")
            object.__setattr__(self, "fault", fts)
        if self.autoscale is None:
            return
        pols = (tuple(self.autoscale)
                if isinstance(self.autoscale, (tuple, list))
                else (self.autoscale,))
        if not pols:
            raise ValueError("autoscale= needs at least one policy "
                             "(or None for a static grid)")
        for pol in pols:
            if not isinstance(pol, AutoscalePolicy):
                raise TypeError(
                    "autoscale must hold AutoscalePolicy values; got "
                    f"{type(pol).__name__}")
        if self.r.shape[0] != 1 or float(self.r[0]) != 1.0:
            raise ValueError(
                "a policy grid replaces the replica axis; leave r at "
                "its default (each policy's max_r sets provisioning)")
        object.__setattr__(self, "autoscale", pols)

    @classmethod
    def build(cls, *, lam: ArrayLike, p: ArrayLike = 100.0,
              cpu: ArrayLike = 1.0, disk: ArrayLike = 1.0,
              hit: ArrayLike = None, memory: int = 1,
              base: Optional[ServerParams] = None,
              broker_from_p: bool = True,
              r: ArrayLike = 1.0,
              result_cache: Optional[tuple[float, float]] = None,
              autoscale=None,
              fault=None,
              ) -> "SweepGrid":
        """Grid from explicit axes; defaults come from Table 6 ``memory``."""
        if base is None:
            s_hit, s_miss, s_disk, h = capacity.MEMORY_TABLE[memory]
            base = ServerParams(p=100, s_broker=capacity.broker_service_time(100),
                                s_hit=s_hit, s_miss=s_miss, s_disk=s_disk,
                                hit=h)
        if hit is None:
            hit = base.hit
        return cls(lam=_axis(lam), p=_axis(p), cpu=_axis(cpu),
                   disk=_axis(disk), hit=_axis(hit), base=base,
                   broker_from_p=broker_from_p, r=_axis(r),
                   result_cache=result_cache, autoscale=autoscale,
                   fault=fault)

    @property
    def shape(self) -> tuple[int, ...]:
        if self.autoscale is not None:
            last = len(self.autoscale)
        elif self.fault is not None:
            last = len(self.fault)
        else:
            last = self.r.shape[0]
        return (self.lam.shape[0], self.p.shape[0], self.cpu.shape[0],
                self.disk.shape[0], self.hit.shape[0], last)

    @property
    def n_scenarios(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    def broadcast(self) -> tuple[Array, ServerParams]:
        """(lam, params) with every field shaped to broadcast over `shape`.

        ``lam`` is the total arrival rate; divide by :meth:`lam_replica`'s
        denominator (the broadcast ``r`` axis) for per-replica rates.
        """
        lam = self.lam.reshape(-1, 1, 1, 1, 1, 1)
        p = self.p.reshape(1, -1, 1, 1, 1, 1)
        cpu = self.cpu.reshape(1, 1, -1, 1, 1, 1)
        disk = self.disk.reshape(1, 1, 1, -1, 1, 1)
        hit = self.hit.reshape(1, 1, 1, 1, -1, 1)
        if self.broker_from_p:
            s_broker = capacity.broker_service_time(p) / cpu
        else:
            s_broker = jnp.asarray(self.base.s_broker, jnp.float32) / cpu
        params = ServerParams(
            p=p,
            s_broker=s_broker,
            s_hit=jnp.asarray(self.base.s_hit, jnp.float32) / cpu,
            s_miss=jnp.asarray(self.base.s_miss, jnp.float32) / cpu,
            s_disk=jnp.asarray(self.base.s_disk, jnp.float32) / disk,
            hit=hit,
        )
        return lam, params

    def lam_replica(self) -> Array:
        """Per-replica arrival rate, broadcastable over `shape`."""
        if self.autoscale is not None:
            raise ValueError(
                "per-replica rates are undefined on a policy grid: the "
                "active replica count varies over time (simulate instead)")
        lam, _ = self.broadcast()
        return lam / self.r.reshape(1, 1, 1, 1, 1, -1)

    def broadcast_full(self) -> tuple[Array, ServerParams]:
        """Like `broadcast`, but every array materialized to `shape`.

        The returned ``lam`` is still the TOTAL rate (the simulator's
        dispatcher does the splitting).
        """
        lam, params = self.broadcast()
        shape = self.shape
        full = {
            f.name: jnp.broadcast_to(
                jnp.asarray(getattr(params, f.name), jnp.float32), shape)
            for f in dataclasses.fields(ServerParams)
        }
        return jnp.broadcast_to(lam, shape), ServerParams(**full)


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Dense response surfaces, all shaped `grid.shape` = (L,P,C,D,H,R)."""

    grid: SweepGrid
    response_lower: Array   # Eq 7 lower bound (s); +inf where saturated
    response_upper: Array   # Eq 7 upper bound (s); the planning metric
    utilization: Array      # index-server utilization lambda * S

    @property
    def response(self) -> Array:
        """The conservative (paper-default) planning surface."""
        return self.response_upper

    @property
    def feasible_fraction(self) -> Array:
        return jnp.mean(jnp.isfinite(self.response_upper))

    def quantile(self, q: float) -> Array:
        """Analytic q-percentile upper estimate over the grid (Sec 7).

        Mirrors :meth:`SimSweepResult.quantile` so frontier extraction can
        target tail latency against either surface.  With a grid-level
        result cache the surface is the Eq-8-style mixture of the no-cache
        quantile and the cache queue's exponential quantile (an upper
        blend — the true quantile of a mixture is below it in the tail).
        """
        _, params = self.grid.broadcast()
        lam_rep = self.grid.lam_replica()
        surf = queueing.response_time_quantile_upper(lam_rep, params, q)
        if self.grid.result_cache is not None:
            hit_r, s_cache = self.grid.result_cache
            r_cache = queueing.mm1_residence_time(lam_rep, s_cache)
            t_cache = -r_cache * jnp.log1p(-jnp.asarray(q, jnp.float32))
            surf = surf * (1.0 - hit_r) + t_cache * hit_r
        return jnp.broadcast_to(surf, self.grid.shape)


def _check_sweep_mesh(mesh) -> tuple[str, int]:
    """Validate a scenario-sharding mesh; returns (axis_name, n_devices).

    Both sweep paths shard over ONE named axis (scenarios are
    embarrassingly parallel), so the mesh must be 1-D — build it with
    `repro.launch.mesh.make_sweep_mesh`.
    """
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"scenario sharding needs a 1-D mesh; got axes "
            f"{tuple(mesh.axis_names)} (build one with "
            "repro.launch.mesh.make_sweep_mesh)")
    return mesh.axis_names[0], int(mesh.devices.size)


@functools.partial(jax.jit, static_argnames=("result_cache",))
def _bounds_surface(lam: Array, params: ServerParams,
                    result_cache=None):
    lo, hi = queueing.response_time_bounds(lam, params)
    if result_cache is not None:
        hit_r, s_cache = result_cache
        # upper: the Eq 8 mixture (queueing.apply_result_cache is the one
        # home of the convention: conservative, load NOT thinned).  That
        # conservatism is only valid UPWARD — for the lower bound both
        # legs use the mechanistically thinned rates (hits really do
        # bypass the servers), so lo stays a genuine lower bound.
        hi = queueing.apply_result_cache(hi, lam, hit_r, s_cache)
        lo_thin, _ = queueing.response_time_bounds(
            lam * (1.0 - hit_r), params)
        r_cache_thin = queueing.mm1_residence_time(lam * hit_r, s_cache)
        lo = lo_thin * (1.0 - hit_r) + r_cache_thin * hit_r
    util = queueing.utilization(lam, queueing.service_time_server(params))
    return lo, hi, util


def sweep_analytical(grid: SweepGrid, *, mesh=None) -> SweepResult:
    """Evaluate Eq 7/Eq 8 bounds over the whole grid as one jitted call.

    Replicated cells are evaluated at the per-replica rate ``lam / r``
    (replication splits arrivals evenly — the paper's linear-gain
    assumption, which `sweep_simulated` cross-checks under real routing).

    ``mesh`` — a 1-D device mesh from `repro.launch.mesh.make_sweep_mesh`
    — shards the flattened scenario axis across devices with
    `compat.shard_map`: the bounds are pure elementwise math, so an
    N-scenario grid splits into N/n_devices-sized shards with zero
    communication.  The grid is padded (edge-replicated) to a device
    multiple and the padding sliced off, so any grid size works.  This is
    how the million-scenario planning surfaces in
    ``examples/global_sweep.py`` are evaluated.
    """
    if grid.autoscale is not None:
        raise ValueError(
            "sweep_analytical cannot evaluate a policy grid: the Eq 7/8 "
            "bounds assume a fixed replica count (use sweep_simulated)")
    if grid.fault is not None:
        raise ValueError(
            "sweep_analytical cannot evaluate a fault grid: the Eq 7/8 "
            "bounds assume every replica is up (use sweep_simulated)")
    lam_rep = grid.lam_replica()
    _, params = grid.broadcast()
    shape = grid.shape
    if mesh is None:
        lo, hi, util = _bounds_surface(lam_rep, params, grid.result_cache)
        return SweepResult(
            grid=grid,
            response_lower=jnp.broadcast_to(lo, shape),
            response_upper=jnp.broadcast_to(hi, shape),
            utilization=jnp.broadcast_to(util, shape),
        )

    axis, n_dev = _check_sweep_mesh(mesh)
    n = grid.n_scenarios
    pad = (-n) % n_dev

    def flat(x):
        x = jnp.broadcast_to(jnp.asarray(x, jnp.float32), shape).reshape(-1)
        return jnp.pad(x, (0, pad), mode="edge") if pad else x

    lam_flat = flat(lam_rep)
    params_flat = ServerParams(**{
        f.name: flat(getattr(params, f.name))
        for f in dataclasses.fields(ServerParams)})
    spec = PartitionSpec(axis)
    fn = functools.partial(_bounds_surface, result_cache=grid.result_cache)
    lo, hi, util = compat.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
        check_vma=False)(lam_flat, params_flat)
    unflat = lambda x: x[:n].reshape(shape)  # noqa: E731
    return SweepResult(
        grid=grid,
        response_lower=unflat(lo),
        response_upper=unflat(hi),
        utilization=unflat(util),
    )


@dataclasses.dataclass(frozen=True)
class SimSweepResult:
    """Streaming-simulated surfaces: mean, spread AND quantiles.

    ``stats`` is a :class:`repro.core.simulator.SimResult` whose fields
    all carry the full grid shape (L,P,C,D,H,R) in front (the histogram
    has one trailing bin axis), so every summary the streaming engine
    accumulates is available as a dense surface.
    """

    grid: SweepGrid
    stats: simulator.SimResult

    @property
    def mean(self) -> Array:
        return self.stats.mean_response

    @property
    def response(self) -> Array:
        """The default planning surface for frontier extraction."""
        return self.mean

    @property
    def std(self) -> Array:
        return self.stats.std_response

    def quantile(self, q: float) -> Array:
        """q-quantile response surface, shaped `grid.shape`."""
        return self.stats.quantile(q)

    @property
    def sample_response(self) -> Array:
        """(L,P,C,D,H,R, tap_size) reservoir sample of per-query responses.

        NaN-padded when a scenario saw fewer post-warmup queries than the
        tap size; empty trailing axis unless the sweep ran with
        ``tap_size > 0``.  This is calibration's trace source for swept
        simulated systems (`repro.calibrate.measure.traces_from_sweep`).
        """
        return self.stats.tap_response


def _sharded_batch(run, mesh, key, proc: ArrivalProcess,
                   params: ServerParams) -> simulator.SimResult:
    """Scenario-shard one (p, r) batch dispatch over a 1-D mesh.

    ``run(key, proc, params)`` is the already-parameterized batch entry
    (all static knobs bound).  The slab's scenario axis is padded
    (edge-replicated) to a device multiple, every leading-axis input is
    sharded with one ``PartitionSpec(axis)``, and each device draws from
    its OWN key (``jax.random.split(key, n_devices)``) — so sharded
    surfaces are statistically equivalent but not bit-identical to the
    unsharded ones.  Every `SimResult` leaf leads with the scenario
    axis, so a single spec works as the out-spec pytree prefix; padded
    scenarios are sliced off before returning.

    The mapped function runs under ``jax.jit``: a leaf with no elements
    (``tap_response`` at ``tap_size=0``) comes back from XLA replicated,
    which eager ``shard_map`` rejects against its scenario out-spec.
    """
    axis, n_dev = _check_sweep_mesh(mesh)
    n_slab = proc.rates.shape[0]
    pad = (-n_slab) % n_dev
    rates = jnp.pad(proc.rates, ((0, pad), (0, 0)), mode="edge") \
        if pad else proc.rates
    params = jax.tree_util.tree_map(
        lambda x: jnp.pad(x, ((0, pad),), mode="edge"), params) \
        if pad else params
    keys = jax.random.split(key, n_dev)
    bin_seconds = proc.bin_seconds
    spec = PartitionSpec(axis)

    def shard_fn(keys_d, rates_d, params_d):
        proc_d = ArrivalProcess.piecewise(rates_d, bin_seconds)
        return run(keys_d[0], proc_d, params_d)

    res = jax.jit(compat.shard_map(
        shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False))(keys, rates, params)
    return jax.tree_util.tree_map(lambda x: x[:n_slab], res)


def _static_count(x, axis_name: str) -> int:
    v = int(round(float(x)))
    if abs(v - float(x)) > 1e-3:
        raise ValueError(
            f"simulation needs integer {axis_name} counts; got {x} "
            "(the analytical path accepts fractional values)")
    return v


def sweep_simulated(
    grid: SweepGrid,
    key: Array,
    *,
    n_queries: int = 20_000,
    mode: str = "exponential",
    impl: str = "xla",
    warmup_fraction: float = 0.1,
    chunk_size: int = simulator.DEFAULT_CHUNK,
    hist_bins: int = simulator.DEFAULT_HIST_BINS,
    tap_size: int = 0,
    profile: Optional[Array] = None,
    profile_bin_seconds: float = 3600.0,
    cluster: Optional[ClusterSpec] = None,
    routing: Optional[str] = None,
    replica_impl: Optional[str] = None,
    telemetry: Optional[simulator.TelemetrySpec] = None,
    mesh=None,
) -> SimSweepResult:
    """Streaming-simulated response surfaces over the grid.

    One streaming dispatch per distinct (p, r) pair (static shapes);
    within a dispatch all L*C*D*H scenarios run as one `lax.scan` over
    query chunks.  Peak memory is n_scenarios_per_dispatch * r * p *
    chunk_size floats — the total query count only adds scan iterations,
    so `n_queries` can be 10-100x what the old materializing path could
    hold.

    ``cluster=ClusterSpec(...)`` supplies the per-dispatch topology
    (routing policy, result cache, replica engine); the grid's own axes
    supply what varies, so ``ClusterSpec.r`` must stay at its default
    (the ``grid.r`` axis is the replica sweep) and
    ``ClusterSpec.autoscale`` must be None (policies go on
    ``SweepGrid(autoscale=...)`` so they form a sweep axis).  The loose
    ``routing=`` / ``replica_impl=`` keywords keep working through the
    `repro.core.cluster.resolve_cluster` deprecation shim.  A
    ``result_cache`` may live on the spec or on the grid but not both.

    Replicated cells (``grid.r``) run the dispatcher topology under
    the spec's routing ("round_robin" | "random" | "jsq"); each
    scenario's lam stays the total rate, so the surface directly
    cross-checks the analytical ``lam / r`` splitting assumption,
    imbalance included.  The effective ``result_cache`` switches on the
    simulator's mechanistic Eq 8 dispatcher cache in every dispatch.

    ``grid.autoscale`` swaps the replica axis for a POLICY axis: one
    dispatch per `AutoscalePolicy`, each provisioning ``max_r`` replicas
    with the policy deciding how many are active per chunk.  Every cell
    then carries ``stats.replica_seconds`` / ``stats.elapsed_seconds``
    (the autoscaler's cost integral), which `extract_frontier` uses to
    price policies by time-averaged fleet size.

    ``grid.fault`` swaps the replica axis for a FAULT-SCENARIO axis
    instead: one dispatch per `repro.core.faults.FaultSpec` (None
    entries are the fault-free baseline), every cell at the grid's one
    fixed replica count.  Simulation-only like policy grids; the cells'
    ``stats.spill_count`` / ``degraded_count`` channels come back with
    the grid shape, so degraded-vs-full-quorum frontiers read straight
    off the sweep (see ``examples/failover_stress.py``).

    ``profile`` makes the load non-stationary: a (n_bins,) relative-rate
    curve (e.g. `repro.workloadgen.loadgen.diurnal_rates`) that tiles with
    period ``n_bins * profile_bin_seconds``.  It is normalized to mean 1,
    so the grid's lam axis stays the *time-averaged* rate and the peak
    rate is ``lam * max(profile)/mean(profile)``.

    ``tap_size > 0`` carries the simulator's bounded reservoir tap through
    every scenario, surfacing a uniform sample of raw per-query response
    times on :attr:`SimSweepResult.sample_response` (calibration's trace
    source) without re-materializing sample paths.

    ``replica_impl`` passes through to the simulator: "fused" (default)
    routes + compacts + segment-scans each chunk in one kernel pass with
    r-independent peak memory; "masked" is the r-times-the-work oracle.

    ``telemetry=TelemetrySpec(...)`` streams the per-time-bin
    `repro.obs.timeline.Timeline` through every dispatch: the
    ``stats.timeline`` leaves come back with the full grid shape in
    front (e.g. utilization is (L,P,C,D,H,R, n_bins, r, p)).  None (the
    default) is the bit-identical pre-telemetry program.

    ``mesh`` — a 1-D device mesh from `repro.launch.mesh.make_sweep_mesh`
    — shards each dispatch's L*C*D*H scenario slab across devices via
    `compat.shard_map` (scenarios never communicate, so the program is
    pure SPMD).  Slabs are padded (edge-replicated) to a device multiple
    and sliced back; each device streams its shard with its OWN PRNG key,
    so sharded surfaces are statistically equivalent, not bit-identical,
    to unsharded ones.
    """
    spec = resolve_cluster(cluster, routing=routing,
                           replica_impl=replica_impl,
                           caller="sweep_simulated")
    if spec.r != 1:
        raise ValueError(
            "sweep_simulated takes replica counts from the grid's r "
            "axis; leave ClusterSpec.r at its default")
    if spec.autoscale is not None:
        raise ValueError(
            "autoscale policies form a sweep axis: put them on "
            "SweepGrid(autoscale=...) rather than the ClusterSpec")
    if spec.fault is not None:
        raise ValueError(
            "fault scenarios form a sweep axis: put them on "
            "SweepGrid(fault=...) rather than the ClusterSpec")
    if spec.result_cache is not None and grid.result_cache is not None:
        raise ValueError(
            "result_cache given on both the ClusterSpec and the grid; "
            "keep exactly one")
    cache = (spec.result_cache if spec.result_cache is not None
             else grid.result_cache)
    policies = grid.autoscale
    faults = grid.fault
    if telemetry is not None and policies is not None:
        max_rs = {pol.max_r for pol in policies}
        if len(max_rs) > 1:
            raise ValueError(
                "telemetry timelines stack a per-replica axis across "
                "policy cells, so every policy needs the same max_r; "
                f"got {sorted(max_rs)}")
    shape = grid.shape
    lam_full, params_full = grid.broadcast_full()

    # hoisted slab extraction: ONE moveaxis/reshape per field up front —
    # (L,P,C,D,H,R) -> (P, R, L*C*D*H) — so every (p, r) dispatch just
    # indexes a row instead of re-gathering its slab from the 6-D tensor
    def slab(x):
        return jnp.moveaxis(x, (1, 5), (0, 1)).reshape(
            shape[1], shape[5], -1)

    lam_slabs = slab(lam_full)
    field_slabs = {f.name: slab(getattr(params_full, f.name))
                   for f in dataclasses.fields(ServerParams)}
    if profile is not None:
        base_proc = ArrivalProcess.piecewise(
            jnp.asarray(profile), profile_bin_seconds).normalized()

    n_p, n_cfg = grid.p.shape[0], shape[5]
    # host-side reads of the static axes: np.asarray on the concrete
    # grid arrays stays concrete even under an ambient trace, whereas
    # grid.p[i] would become a tracer and break float() — this keeps
    # sweep_simulated runnable under jax.eval_shape (the staticcheck
    # shape contract) with an abstract lam axis
    p_axis = np.asarray(grid.p)
    r_axis = None if policies is not None else np.asarray(grid.r)
    # flat indexing (no reshape) keeps both legacy uint32 and new-style
    # typed PRNG keys working: split always yields a 1-D sequence of keys
    keys = jax.random.split(key, n_p * n_cfg)

    def dispatch(k, lam_ij, params_ij, p: int, cell: ClusterSpec):
        """The single batch entry shared by every (p, config) cell.

        All cells with equal static (p, cell) and slab shape reuse one
        compiled program (jit caches on statics + avals); sharding wraps
        the SAME bound entry in `_sharded_batch`, so the mesh path and
        the local path cannot drift apart.
        """
        arrival = (ArrivalProcess.stationary(lam_ij) if profile is None
                   else base_proc.scaled_by(lam_ij))
        # profile-fidelity chunk clamp happens HERE, host-side, where the
        # rates are still concrete — under shard_map they are tracers and
        # the simulator's internal clamp deliberately no-ops
        chunk = simulator._clamp_chunk_for_profile(
            arrival, max(1, min(chunk_size, n_queries)))
        run = functools.partial(
            simulator.simulate_fork_join_batch, n_queries=n_queries,
            p=p, mode=mode, impl=impl, warmup_fraction=warmup_fraction,
            chunk_size=chunk, hist_bins=hist_bins, tap_size=tap_size,
            cluster=cell, telemetry=telemetry)
        if mesh is None:
            return run(k, arrival, params_ij)
        return _sharded_batch(run, mesh, k, arrival, params_ij)

    def fill_fault_channels(res, r: int):
        """Zero-filled fault channels for the ``fault=None`` baseline cell.

        A fault axis may mix FaultSpec cells with a fault-free baseline;
        the baseline's SimResult carries ``None`` in the fault slots,
        which would break the pytree stack across cells.  Materialize
        the semantically-equal constants instead: nothing spilled or
        degraded, every replica up for every arrival.
        """
        if res.spill_count is not None:
            return res
        z = jnp.zeros_like(res.count)
        kw = dict(spill_count=z, unavail_count=z, degraded_count=z)
        if res.timeline is not None and res.timeline.up_sum is None:
            tl = res.timeline
            kw["timeline"] = dataclasses.replace(
                tl, up_sum=tl.count * float(r),
                spill_sum=jnp.zeros_like(tl.count),
                degraded_sum=jnp.zeros_like(tl.count))
        return dataclasses.replace(res, **kw)

    results = []
    for i in range(n_p):
        p = _static_count(p_axis[i], "server")
        cfg_results = []
        for j in range(n_cfg):
            if policies is not None:
                cell = ClusterSpec(routing=spec.routing,
                                   result_cache=cache,
                                   replica_impl=spec.replica_impl,
                                   autoscale=policies[j])
            elif faults is not None:
                cell = ClusterSpec(r=_static_count(r_axis[0], "replica"),
                                   routing=spec.routing,
                                   result_cache=cache,
                                   replica_impl=spec.replica_impl,
                                   fault=faults[j])
            else:
                cell = ClusterSpec(r=_static_count(r_axis[j], "replica"),
                                   routing=spec.routing,
                                   result_cache=cache,
                                   replica_impl=spec.replica_impl)
            params_ij = ServerParams(
                **{n: v[i, j] for n, v in field_slabs.items()})
            # one span per (p, config) cell: the dispatch returns as soon
            # as the program is enqueued, so the span is host-side work
            with span("sweep.dispatch", p=p, r=cell.r):
                res = dispatch(keys[i * n_cfg + j], lam_slabs[i, j],
                               params_ij, p, cell)
            if faults is not None:
                res = fill_fault_channels(res, cell.r)
            cfg_results.append(res)
        results.append(cfg_results)
    with span("sweep.gather"):
        slab_shape = (shape[0], shape[2], shape[3], shape[4])
        # stack the replica/policy axis behind (L,C,D,H) -> axis 4
        p_slabs = [jax.tree_util.tree_map(
            lambda *xs: jnp.stack(
                [x.reshape(slab_shape + x.shape[1:]) for x in xs], axis=4),
            *cfg_results) for cfg_results in results]
        # stack the p axis into position 1 -> (L,P,C,D,H,R)
        stats = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=1), *p_slabs)
    return SimSweepResult(grid=grid, stats=stats)


def default_config_cost(p: Array, cpu: Array, disk: Array,
                        hit: Array) -> Array:
    """Illustrative hardware cost: servers are the unit.

    Each server costs 1 baseline, plus 0.5 per unit of extra CPU speed,
    0.25 per unit of extra disk speed, and up to 1.0 for the memory that
    buys a high disk-cache hit ratio.  Replace via the ``cost_fn``
    argument of :func:`extract_frontier` for a real procurement model.
    """
    per_server = (1.0 + 0.5 * (cpu - 1.0) + 0.25 * (disk - 1.0)
                  + 1.0 * hit)
    return p * per_server


@dataclasses.dataclass(frozen=True)
class Frontier:
    """Per-lambda cheapest feasible configuration (all arrays (L,)).

    On a policy grid ``r`` is the chosen policy's MEAN ACTIVE replica
    count (``replica_seconds / elapsed_seconds`` — generally fractional)
    and ``autoscale`` holds the chosen `AutoscalePolicy` per rate;
    otherwise ``autoscale`` is None and ``r`` is the static count.  On a
    fault grid ``fault`` holds the chosen cell's `FaultSpec` (or None
    for the fault-free baseline cell) per rate — the harshest-surviving
    scenario when the surface is fed through a min, or simply the
    cheapest feasible cell under the default argmin.
    """

    lam: Array
    feasible: Array    # bool: any config meets the SLO at this rate
    cost: Array        # cost of the chosen config; +inf if infeasible
    p: Array
    cpu: Array
    disk: Array
    hit: Array
    response: Array    # targeted-surface response of the chosen config (s)
    r: Array = None    # replicas of the chosen config ((L,); 1s pre-grid)
    autoscale: Optional[tuple[AutoscalePolicy, ...]] = None
    fault: Optional[tuple[Optional[FaultSpec], ...]] = None

    def describe(self, i: int) -> str:
        if not bool(self.feasible[i]):
            return (f"lam={float(self.lam[i]):g} qps: INFEASIBLE "
                    f"anywhere on the grid")
        if self.autoscale is not None:
            pol = self.autoscale[i]
            rep_s = (f" autoscale {pol.min_r}..{pol.max_r}"
                     f" @{pol.target_utilization:.0%}"
                     f" (mean active {float(self.r[i]):.2f})")
        else:
            reps = 1 if self.r is None else int(round(float(self.r[i])))
            rep_s = f" x{reps} replicas" if reps != 1 else ""
            if self.fault is not None:
                ft = self.fault[i]
                rep_s += (" (fault-free)" if ft is None
                          else f" under {ft!r}")
        return (f"lam={float(self.lam[i]):g} qps: p={float(self.p[i]):g} "
                f"cpu x{float(self.cpu[i]):g} disk x{float(self.disk[i]):g} "
                f"hit={float(self.hit[i]):.2f}{rep_s} -> "
                f"R<={float(self.response[i]) * 1e3:.0f} ms "
                f"(cost {float(self.cost[i]):.1f})")


def extract_frontier(
    result: Union[SweepResult, SimSweepResult],
    slo_seconds: float,
    *,
    cost_fn: Optional[Callable[[Array, Array, Array, Array], Array]] = None,
    surface: Optional[Array] = None,
    quantile: Optional[float] = None,
) -> Frontier:
    """Cheapest config whose response surface meets the SLO, per lambda.

    The targeted surface defaults to ``result.response`` (the Eq 7 upper
    bound for analytical sweeps, the simulated mean for streaming sweeps).
    Pass ``quantile=0.95`` to plan against tail latency instead — "the
    cheapest configuration whose p95 survives the load" — or hand any
    precomputed ``surface`` shaped `grid.shape`.

    Fully vectorized: the (P,C,D,H,R) config-cost tensor is masked by the
    feasibility surface and argmin-reduced per arrival rate.  ``cost_fn``
    prices ONE replica's hardware (p, cpu, disk, hit); replication
    multiplies it — r copies of the cluster cost r times as much.

    On a policy grid the replica multiplier is not a grid constant: each
    cell is priced by its OBSERVED time-averaged fleet size
    ``replica_seconds / elapsed_seconds`` (the autoscaler's cost
    integral), so "cheapest" means fewest replica-seconds per second —
    directly comparable to a static-r plan's ``cost * r`` at the same
    SLO compliance.
    """
    grid = result.grid
    if surface is None:
        surface = (result.quantile(quantile) if quantile is not None
                   else result.response)
    cost_fn = cost_fn or default_config_cost
    costs = cost_fn(
        grid.p.reshape(-1, 1, 1, 1),
        grid.cpu.reshape(1, -1, 1, 1),
        grid.disk.reshape(1, 1, -1, 1),
        grid.hit.reshape(1, 1, 1, -1),
    )
    costs = jnp.broadcast_to(costs, grid.shape[1:5])
    if grid.autoscale is not None:
        stats = getattr(result, "stats", None)
        if stats is None or stats.replica_seconds is None:
            raise ValueError(
                "a policy grid prices configurations by simulated "
                "replica-seconds; extract the frontier from a "
                "sweep_simulated result")
        eff_r = stats.replica_seconds / jnp.maximum(
            stats.elapsed_seconds, 1e-30)             # (L,P,C,D,H,A)
        costs_full = costs[None, :, :, :, :, None] * eff_r
    else:
        eff_r = None
        costs_full = (costs[..., None]
                      * grid.r.reshape(1, 1, 1, 1, -1))[None]

    feasible = surface <= slo_seconds                     # (L,P,C,D,H,R)
    masked = jnp.where(feasible, costs_full, jnp.inf)
    flat = masked.reshape(grid.shape[0], -1)
    best = jnp.argmin(flat, axis=1)
    best_cost = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]

    ip, ic, id_, ih, ir = jnp.unravel_index(best, grid.shape[1:])
    chosen_resp = jnp.take_along_axis(
        surface.reshape(grid.shape[0], -1),
        best[:, None], axis=1)[:, 0]
    any_feasible = jnp.isfinite(best_cost)
    chosen_fault = None
    if grid.autoscale is not None:
        chosen_r = jnp.take_along_axis(
            eff_r.reshape(grid.shape[0], -1), best[:, None], axis=1)[:, 0]
        chosen_pol = tuple(grid.autoscale[int(t)] for t in np.asarray(ir))
    elif grid.fault is not None:
        # fault cells all run at the one fixed replica count; the 6th
        # index picks the failure scenario, not the fleet size
        chosen_r = jnp.broadcast_to(grid.r[:1], ir.shape)
        chosen_pol = None
        chosen_fault = tuple(grid.fault[int(t)] for t in np.asarray(ir))
    else:
        chosen_r = grid.r[ir]
        chosen_pol = None
    return Frontier(
        lam=grid.lam,
        feasible=any_feasible,
        cost=best_cost,
        p=grid.p[ip],
        cpu=grid.cpu[ic],
        disk=grid.disk[id_],
        hit=grid.hit[ih],
        response=chosen_resp,
        r=chosen_r,
        autoscale=chosen_pol,
        fault=chosen_fault,
    )
