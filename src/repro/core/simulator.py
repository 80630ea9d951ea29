"""Streaming max-plus discrete-event simulator for fork-join search clusters.

The paper validates its model on an 8-node physical cluster and leaves
"simulation-based analysis ... for larger clusters with thousands of index
servers" as future work.  This module delivers that in JAX.

Key idea: FCFS queueing is a linear recurrence in the (max, +) semiring.
With arrival times A_i (sorted) and service times S_i, the completion time

    C_i = S_i + max(A_i, C_{i-1})  =  max(a_i, C_{i-1} + b_i),
          a_i = A_i + S_i,  b_i = S_i

and the affine maps c -> max(a, c + b) compose associatively:

    (a1,b1) then (a2,b2)  =  (max(a2, a1 + b2), b1 + b2)

so a whole sample path is one associative scan — and, because the maps
compose, FCFS state *streams*: the engine scans fixed-size query chunks
with ``jax.lax.scan``, carrying only the per-(scenario, server) last
completion times plus running statistics (count, sum, sum of squares and
a fixed-bin log histogram of response times for quantiles).  Peak memory
is S x p x chunk floats regardless of the total query count, so grids
10-100x larger than the old materializing engine fit, and simulated
horizons of millions of queries stream through unchanged.  Within a chunk
the scan runs either as `jax.lax.associative_scan` or as the Pallas TPU
kernel (`repro.kernels.maxplus_scan`), seeded from the carry via its
``maxplus_scan_seeded`` entry point.

Arrivals come from an :class:`repro.core.arrivals.ArrivalProcess`:
stationary Poisson, piecewise-rate diurnal/weekly profiles (each chunk
draws gaps at the rate read off at its start time — the paper's
Section 4.2 "homogeneous within a window" structure), or a replayed
trace.  Scalar rates are promoted to stationary processes, so existing
call sites keep working.

Simulated system (paper Fig 8): broker FCFS queue -> fork to p index-server
FCFS queues -> join (max over servers) -> response = join - arrival.

Replication (paper Sec 6, ``replicas_needed``): with ``r > 1`` the network
grows a front-end dispatcher that routes each query to ONE of r identical
replicas, each a full broker + p-server fork-join.  Routing policies:

  * "round_robin" — query i goes to replica i mod r (deterministic);
  * "random"      — iid uniform replica choice (Poisson thinning);
  * "jsq"         — join-shortest-queue on *carried per-replica work*: a
    fluid backlog tracker (per-replica, per-server remaining seconds)
    rides in the scan carry, and each query picks the replica whose
    slowest server frees up first.

The replicated network runs FUSED by default (``replica_impl="fused"``):
routing choices become an integer assignment per query, each chunk is
compacted so every replica's queries are contiguous (a pure reshape for
round-robin when chunk % r == 0; a stable sort otherwise), and ONE
segmented (max, +) scan per queue level covers all r replicas — each
query is scanned once on its own replica's queues, so per-chunk work is
S x p x chunk elements *independent of r* and the working set shrinks by
the same factor.  Per-replica carries seed the segment heads and are
read back off the segment ends, so the streaming chunk chain is
unchanged.  ``replica_impl="masked"`` keeps the original oracle: every
replica re-scans the FULL stream with zero-service "phantoms" for
queries routed elsewhere (a phantom C_i = max(A_i, C_{i-1}) can never
delay a later real query since arrivals are nondecreasing —
max(A_j, max(A_i, C)) = max(A_j, C) for A_j >= A_i).  The same argument
shows the two implementations produce identical sample paths in exact
arithmetic; the masked path costs ~r x more and survives only as the
equality-test reference.

An optional broker-level result cache (``result_cache=(hit_r, s_cache)``)
short-circuits service: each query is a cache hit with probability hit_r
and is then served by its replica's broker-cache FCFS queue with
Exp(s_cache) service instead of forking to the index servers — the
mechanistic counterpart of Eq 8, placed exactly where the paper puts it
(at each cluster's broker, so the analytic Eq 8 term at lam / r and the
simulated cache queue describe the same system).  Unlike the paper's
conservative bound the simulator DOES thin the index-server load, so
simulated means sit at or below the Eq 8 bound.

Topology lives on ONE static argument: ``cluster=ClusterSpec(r=...,
routing=..., result_cache=..., replica_impl=..., autoscale=...)`` (see
`repro.core.cluster`).  The loose keywords of the same names keep
working through a once-warning deprecation shim.

Elastic autoscaling (``ClusterSpec(autoscale=AutoscalePolicy(...))``)
makes the ACTIVE replica count time-varying: the engine provisions
``max_r`` replicas, and the HPA-shaped controller of
`repro.launch.elastic` rides the scan carry — per query it drains a
fluid backlog, accumulates utilization feedback, and at each decision
interval steps the active count inside [min_r, max_r].  Routing only
targets active replicas (round-robin wraps at n_active, random thins
over n_active, JSQ masks inactive candidates); scale-out replicas start
cold (their carries sit at the drained state) and scale-in replicas
drain in-flight work before going quiet.  The run additionally
accumulates the cost integral ``SimResult.replica_seconds`` (and
``elapsed_seconds``), which is what the policy sweeps in
`repro.core.sweep` price.

Service-time generators cover three regimes:

  * "exponential" — iid Exp(S_server) per (query, server): the model's
    assumption, full imbalance across servers.
  * "cache"       — per-(query, server) Bernoulli(hit) mixture of
    Exp(s_hit) vs Exp(s_miss)+Exp(s_disk): the mechanistic story of Sec 3.4.
  * "balanced"    — identical service time for all servers per query: the
    Chowdhury & Pass assumption the paper argues against.

RNG plan: all randomness for chunk c comes from ``fold_in(key, c)`` via
:func:`chunk_random_draws` — one canonical plan used by the streaming
engine and by any monolithic reference reconstruction, so the two are
comparable sample-path-for-sample-path.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import queueing
from repro.core.arrivals import ArrivalProcess
from repro.core.cluster import ClusterSpec, ROUTING_POLICIES, \
    resolve_cluster
from repro.core.faults import FaultSpec, fault_init, fault_scan
from repro.core.queueing import ServerParams, service_time_server
from repro.launch.elastic import AutoscalePolicy, autoscale_init, \
    autoscale_scan
from repro.obs import spans
from repro.obs.timeline import TelemetrySpec, Timeline

Array = jax.Array

__all__ = [
    "maxplus_combine",
    "fcfs_completion_times",
    "fcfs_completion_times_routed",
    "ArrivalProcess",
    "ClusterSpec",
    "AutoscalePolicy",
    "FaultSpec",
    "SimResult",
    "simulate_fork_join",
    "simulate_fork_join_batch",
    "simulate_mmc",
    "sample_service_times_batch",
    "chunk_random_draws",
    "TelemetrySpec",
    "Timeline",
    "DEFAULT_CHUNK",
    "DEFAULT_HIST_BINS",
    "ROUTING_POLICIES",
]

DEFAULT_CHUNK = 4096
DEFAULT_HIST_BINS = 256
# salts for auxiliary RNG streams: folded on top of the per-chunk key
# AFTER chunk_random_draws' fold, so enabling the tap, random routing, or
# the result cache never perturbs the canonical gap/broker/service draws
_TAP_SALT = 0x7EE5
_ROUTE_SALT = 0x2077
_CACHE_SALT = 0xCA8E
_FAULT_SALT = 0xFA17
# log-histogram span, in decades around the per-scenario analytic scale
_HIST_DECADES_BELOW = 3.0
_HIST_DECADES_TOTAL = 6.0


def _kernel_name(base: str, stage: Optional[str]) -> str:
    """The scan kernel's name at a call site of ``stage``."""
    return base if stage is None else f"{base}_{stage}"


def _stage(name: str):
    """Device scope ``stream.<name>`` of one stage of the chunk body."""
    return jax.named_scope("stream." + name)


def maxplus_combine(x, y):
    """Associative composition of affine max-plus maps; y is *later*."""
    a1, b1 = x
    a2, b2 = y
    return jnp.maximum(a2, a1 + b2), b1 + b2


def fcfs_completion_times(arrivals: Array, services: Array,
                          impl: str = "auto",
                          carry: Optional[Array] = None,
                          stage: Optional[str] = None) -> Array:
    """Completion times of an FCFS single-server queue.

    arrivals: (..., n) nondecreasing along the last axis.
    services: (..., n) positive.
    impl: "xla" (associative_scan) or "pallas" (TPU kernel; interpreted
    only on the CPU test backend) — both compute the identical recurrence.  The default
    "auto" picks "pallas" on real TPU hardware and "xla" everywhere
    else (interpret-mode Pallas is slower than associative_scan); see
    `repro.kernels.maxplus_scan.ops.resolve_scan_impl`.
    carry: optional (...,) completion time of the work *before* this
    block; seeding composes it on top of the scan, which is how the
    streaming engine chains chunks.
    stage: the queue level the scan serves (the stream engine passes
    "broker", "server" or "cache"); it names the kernel
    ``maxplus_scan_<stage>`` in device traces.
    """
    if impl == "auto":
        from repro.kernels.maxplus_scan.ops import resolve_scan_impl
        impl = resolve_scan_impl(impl)
    a = arrivals + services
    b = services
    if impl == "pallas":
        from repro.kernels.maxplus_scan import ops as mp_ops
        name = _kernel_name("maxplus_scan", stage)
        if carry is None:
            out_a, _ = mp_ops.maxplus_scan(a, b, name=name)
        else:
            out_a, _ = mp_ops.maxplus_scan_seeded(a, b, carry, name=name)
        return out_a
    out_a, out_b = jax.lax.associative_scan(maxplus_combine, (a, b), axis=-1)
    if carry is not None:
        out_a = jnp.maximum(out_a, jnp.asarray(carry)[..., None] + out_b)
    return out_a


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimResult:
    """Streaming summary statistics of a fork-join simulation.

    Every field carries the run's scenario shape in front (scalar for a
    single-scenario run, ``(S,)`` for batches, the full grid shape after a
    sweep).  Warmup queries are *discarded* from every accumulator — no
    mean-substitution masking, so quantiles are unbiased.

    Quantiles come from a fixed-bin logarithmic response-time histogram:
    ``hist[..., k]`` counts responses in
    ``[exp(log_lo + k*step), exp(log_lo + (k+1)*step))``; under/overflow
    is clamped into the edge bins.

    ``tap_response`` is the ROADMAP's bounded tap: a uniform reservoir
    sample (without replacement) of per-query post-warmup response times,
    carried through the scan at fixed size instead of re-materializing the
    sample path.  Slots not yet filled hold NaN; ``tap_size=0`` (the
    default) disables the tap at zero cost.  `repro.calibrate.measure`
    consumes it as the trace source for simulated systems.

    ``timeline`` is the opt-in per-time-bin telemetry of
    `repro.obs.timeline`: None unless the run passed a
    :class:`TelemetrySpec` (None contributes no pytree leaves, so every
    existing consumer and the eval_shape contract see the same tree).

    ``replica_seconds`` / ``elapsed_seconds`` are the autoscaler's cost
    integral — provisioned replica-seconds and simulated wall seconds
    over the whole run (warmup included; provisioning is paid for from
    t=0).  None unless the run carried an
    :class:`~repro.launch.elastic.AutoscalePolicy`, following the
    timeline convention.

    ``spill_count`` / ``unavail_count`` / ``degraded_count`` are the
    fault channels (None unless the run carried a
    :class:`~repro.core.faults.FaultSpec`, same convention): post-warmup
    queries re-routed off a down replica, queries arriving with NO
    surviving replica to route to, and partial-quorum (k-of-p) results
    cut short by the broker timeout.  The derived ``availability`` /
    ``spill_fraction`` / ``degraded_fraction`` are what capacity plans
    gate on.
    """

    count: Array           # post-warmup samples per scenario
    sum_response: Array
    sumsq_response: Array
    sum_broker: Array      # broker residence sum
    sum_cluster: Array     # fork-join (max over servers) residence sum
    sum_server: Array      # residence at ONE tagged server
    hist: Array            # (..., n_bins) response-time histogram counts
    hist_log_lo: Array     # (...,) ln(lowest bin edge, seconds)
    hist_log_step: Array   # (...,) ln(bin edge ratio)
    tap_response: Array    # (..., tap_size) reservoir sample of responses
    timeline: Optional[Timeline] = None  # per-bin telemetry (see obs)
    replica_seconds: Optional[Array] = None  # integral of active r dt
    elapsed_seconds: Optional[Array] = None  # integral of dt (valid)
    spill_count: Optional[Array] = None      # failover-spilled queries
    unavail_count: Optional[Array] = None    # no surviving replica
    degraded_count: Optional[Array] = None   # k-of-p partial results

    @property
    def _n(self) -> Array:
        return jnp.maximum(self.count, 1.0)

    @property
    def mean_response(self) -> Array:
        return self.sum_response / self._n

    @property
    def var_response(self) -> Array:
        m = self.mean_response
        return jnp.maximum(self.sumsq_response / self._n - m * m, 0.0)

    @property
    def std_response(self) -> Array:
        return jnp.sqrt(self.var_response)

    @property
    def tap_size(self) -> int:
        return self.tap_response.shape[-1]

    @property
    def mean_active_replicas(self) -> Array:
        """Time-average active replica count of an autoscaled run."""
        if self.replica_seconds is None:
            raise ValueError("no autoscaler ran: replica_seconds is only "
                             "recorded under ClusterSpec(autoscale=...)")
        return self.replica_seconds / jnp.maximum(self.elapsed_seconds,
                                                  1e-30)

    def _fault_channel(self, name: str) -> Array:
        val = getattr(self, name)
        if val is None:
            raise ValueError(
                f"no faults were injected: {name} is only recorded "
                "under ClusterSpec(fault=FaultSpec(...))")
        return val

    @property
    def availability(self) -> Array:
        """Fraction of post-warmup queries that found a live replica."""
        return 1.0 - self._fault_channel("unavail_count") / self._n

    @property
    def spill_fraction(self) -> Array:
        """Fraction of queries failed over off a down replica."""
        return self._fault_channel("spill_count") / self._n

    @property
    def degraded_fraction(self) -> Array:
        """Fraction of responses returned on a k-of-p partial quorum."""
        return self._fault_channel("degraded_count") / self._n

    @property
    def mean_broker_residence(self) -> Array:
        return self.sum_broker / self._n

    @property
    def mean_cluster_residence(self) -> Array:
        return self.sum_cluster / self._n

    @property
    def mean_server_residence(self) -> Array:
        return self.sum_server / self._n

    def quantile(self, q: float) -> Array:
        """q-quantile of the response time from the streaming histogram.

        Resolution is one log bin (~2.7% at the default 256 bins over 6
        decades); interpolation inside the bin is log-linear.
        """
        n_bins = self.hist.shape[-1]
        cum = jnp.cumsum(self.hist, axis=-1)
        target = jnp.asarray(q) * self.count
        k = jnp.sum(cum < target[..., None], axis=-1)
        k = jnp.clip(k, 0, n_bins - 1)
        cum_before = jnp.where(
            k > 0,
            jnp.take_along_axis(cum, jnp.maximum(k - 1, 0)[..., None],
                                axis=-1)[..., 0],
            0.0)
        in_bin = jnp.take_along_axis(self.hist, k[..., None],
                                     axis=-1)[..., 0]
        frac = jnp.clip((target - cum_before) / jnp.maximum(in_bin, 1.0),
                        0.0, 1.0)
        return jnp.exp(self.hist_log_lo + (k + frac) * self.hist_log_step)


def sample_service_times_batch(
    key: Array, n_scenarios: int, n_queries: int, p: int,
    params: ServerParams, mode: str,
) -> Array:
    """(n_scenarios, p, n_queries) service times; params fields are (S,).

    The one service-time sampler: every scenario gets independent
    randomness but scenario-specific means/hit ratios, in one pass.
    """
    shape = (n_scenarios, p, n_queries)
    s_mean = service_time_server(params)[:, None, None]
    if mode == "exponential":
        return jax.random.exponential(key, shape) * s_mean
    if mode == "balanced":
        one = jax.random.exponential(key, (n_scenarios, 1, n_queries))
        return jnp.broadcast_to(one * s_mean, shape)
    if mode == "cache":
        k1, k2, k3, k4 = jax.random.split(key, 4)
        hit = jnp.asarray(params.hit)[:, None, None]
        is_hit = jax.random.bernoulli(k1, jnp.broadcast_to(hit, shape))
        t_hit = (jax.random.exponential(k2, shape)
                 * jnp.asarray(params.s_hit)[:, None, None])
        t_miss = (jax.random.exponential(k3, shape)
                  * jnp.asarray(params.s_miss)[:, None, None]
                  + jax.random.exponential(k4, shape)
                  * jnp.asarray(params.s_disk)[:, None, None])
        return jnp.where(is_hit, t_hit, t_miss)
    raise ValueError(f"unknown service mode: {mode}")


def chunk_random_draws(key: Array, chunk_idx, n_scen: int, chunk: int,
                       p: int, params: ServerParams, mode: str,
                       *, with_gaps: bool = True):
    """The canonical per-chunk RNG plan: ``fold_in(key, chunk_idx)``.

    Returns (unit-rate gap draws (S, chunk), unit-mean broker draws
    (S, chunk), service times (S, p, chunk)).  The streaming engine and
    any monolithic reference reconstruction MUST both use this function,
    so their sample paths agree draw-for-draw.  ``with_gaps=False`` skips
    the gap draw (trace replay supplies its own gaps); the broker/service
    subkeys are independent splits, so the other draws are unchanged.
    """
    kc = jax.random.fold_in(key, chunk_idx)
    k_arr, k_brk, k_srv = jax.random.split(kc, 3)
    u_gaps = (jax.random.exponential(k_arr, (n_scen, chunk))
              if with_gaps else None)
    u_broker = jax.random.exponential(k_brk, (n_scen, chunk))
    services = sample_service_times_batch(k_srv, n_scen, chunk, p, params,
                                          mode)
    return u_gaps, u_broker, services


def _vec_params(params: ServerParams) -> ServerParams:
    """Every field at least rank-1 (leading scenario axis)."""
    return ServerParams(**{
        f.name: jnp.atleast_1d(jnp.asarray(getattr(params, f.name)))
        for f in dataclasses.fields(ServerParams)})


def _as_batch_process(arrival: Union[ArrivalProcess, Array, float]
                      ) -> ArrivalProcess:
    """Promote a scalar/vector rate or 1-D process to (S, n_bins) rates."""
    if isinstance(arrival, ArrivalProcess):
        if arrival.rates.ndim == 1:
            return dataclasses.replace(arrival, rates=arrival.rates[None, :])
        if arrival.rates.ndim != 2:
            raise ValueError("ArrivalProcess rates must be (n_bins,) or "
                             f"(S, n_bins); got {arrival.rates.shape}")
        return arrival
    lam = jnp.atleast_1d(jnp.asarray(arrival))
    return ArrivalProcess.stationary(lam)


def _check_trace(proc: ArrivalProcess, n_queries: int) -> None:
    if proc.trace_gaps is not None and proc.trace_gaps.shape[0] < n_queries:
        raise ValueError(
            f"trace has {proc.trace_gaps.shape[0]} arrivals but "
            f"n_queries={n_queries}; shorten the horizon or fold/extend "
            "the trace")


_MIN_PROFILE_CHUNK = 64


def _clamp_chunk_for_profile(proc: ArrivalProcess, chunk: int) -> int:
    """Keep a chunk's expected duration near one profile bin.

    The engine reads the arrival rate once per chunk (at its start time);
    if a chunk spans many profile bins, the diurnal curve is undersampled
    and time-varying results bias low.  For multi-bin profiles, cap the
    chunk at the expected number of queries in the *slowest* bin so every
    bin gets visited — floored at ``_MIN_PROFILE_CHUNK`` so a near-empty
    trough bin cannot degenerate the scan into per-query steps.  A
    ``UserWarning`` reports the clamp (it trades scan iterations for
    profile fidelity; pass a coarser profile or a smaller ``chunk_size``
    to silence it).  Stationary and trace-driven processes are exempt
    (the rate never changes / gaps are exact); traced rates are left
    untouched (call the jitted core directly to opt out).
    """
    if proc.trace_gaps is not None or proc.n_bins == 1:
        return chunk
    try:
        # where-mask (not boolean indexing) so tracer rates fail on the
        # float() below with ConcretizationTypeError — under an ambient
        # trace (eval_shape, shard_map) the clamp deliberately no-ops
        # and callers clamp host-side (see repro.core.sweep)
        pos = jnp.where(proc.rates > 0, proc.rates, jnp.inf)
        min_rate = float(jnp.min(pos))
        bin_s = float(proc.bin_seconds)
    except jax.errors.ConcretizationTypeError:
        return chunk
    if not math.isfinite(min_rate):
        min_rate = 0.0
    if min_rate <= 0.0:
        return chunk
    clamped = max(_MIN_PROFILE_CHUNK, int(min_rate * bin_s))
    if clamped < chunk:
        warnings.warn(
            f"chunk_size clamped {chunk} -> {clamped} so each ~"
            f"{bin_s:g}s profile bin is sampled (slowest bin expects "
            f"~{min_rate * bin_s:.0f} queries); more scan iterations, "
            "faithful diurnal shape", UserWarning, stacklevel=3)
        return clamped
    return chunk


def _routing_assign(routing: str, r: int, key: Array, c_idx, gidx,
                    n_scen: int, chunk: int,
                    n_act: Optional[Array] = None,
                    up: Optional[Array] = None):
    """(S, chunk) integer replica assignment for oblivious policies.

    Returns ``(assign, spill, unavail)``; ``assign`` is None for "jsq"
    (its choice needs the carried work state and is computed inside the
    scan body).  Round-robin assigns by GLOBAL query index, so the
    assignment is invariant to how the stream is chunked.

    ``n_act`` (autoscaling): per-query active replica count (S, chunk).
    Oblivious policies then target only the active fleet — round-robin
    wraps the global index at n_active, random thins uniformly over
    n_active — so inactive replicas receive no new work and drain.

    ``up`` (fault injection): per-query replica-up mask (S, chunk, r)
    from `repro.core.faults.fault_scan`.  Round-robin fails over evenly:
    query g goes to the (g mod n_up)-th surviving (and active) replica
    in index order, n_up the count of those at its arrival, so the
    survivors split the load evenly as the Section 6 sizing assumes;
    with every replica up that is g mod r (or g mod n_active).  Random
    routing spills a query raw-routed to a down replica onto the next
    surviving replica cyclically — the smallest offset j with
    up[(raw + j) % r].  ``spill`` marks queries whose raw replica was
    down, ``unavail`` queries for which no active replica was up (those
    keep their raw assignment: the dispatcher has nowhere better to send
    them, and the availability channel records the incident).  Both are
    None when ``up`` is None, and the assignment is bit-identical to the
    fault-free one.
    """
    if routing == "round_robin":
        if n_act is not None:
            raw = gidx[None, :].astype(jnp.int32) % n_act
        else:
            raw = jnp.broadcast_to((gidx % r)[None, :], (n_scen, chunk))
    elif routing == "random":
        k_route = jax.random.fold_in(
            jax.random.fold_in(key, c_idx), _ROUTE_SALT)
        if n_act is not None:
            u = jax.random.uniform(k_route, (n_scen, chunk))
            raw = jnp.minimum((u * n_act).astype(jnp.int32), n_act - 1)
        else:
            raw = jax.random.randint(k_route, (n_scen, chunk), 0, r)
    else:
        return None, None, None
    if up is None:
        return raw, None, None
    ok = up
    if n_act is not None:
        ok = ok & (jnp.arange(r)[None, None, :] < n_act[:, :, None])
    any_ok = jnp.any(ok, axis=-1)
    raw_ok = jnp.take_along_axis(ok, raw[:, :, None], axis=-1)[..., 0]
    if routing == "round_robin":
        rank = jnp.cumsum(ok.astype(jnp.int32), axis=-1)  # (S, chunk, r)
        nth = gidx[None, :].astype(jnp.int32) % jnp.maximum(rank[..., -1], 1)
        pick = jnp.argmax(ok & (rank == nth[..., None] + 1), axis=-1)
        assign = jnp.where(any_ok, pick.astype(jnp.int32), raw)
    else:
        cand = (raw[:, :, None] + jnp.arange(r)[None, None, :]) % r
        ok_c = jnp.take_along_axis(ok, cand, axis=-1)     # (S, chunk, r)
        j = jnp.argmax(ok_c, axis=-1).astype(jnp.int32)   # first ok offset
        assign = jnp.where(any_ok, (raw + j) % r, raw)
    return assign, any_ok & ~raw_ok, ~any_ok


def _jsq_route(w: Array, gaps: Array, services: Array, live: Array,
               r: int, dtype,
               n_act: Optional[Array] = None,
               up: Optional[Array] = None):
    """Join-shortest-queue on carried per-replica work (fluid backlog).

    w: (S, r, p) remaining seconds of work per replica server, measured
    at the previous arrival.  For each query (a cheap sequential scan —
    JSQ is state-dependent, so this is irreducible): drain every tracker
    by the interarrival gap, pick the replica whose *slowest* server
    frees first (the join is what the query waits for), and add the
    query's drawn per-server service times to that replica's trackers.
    ``live`` zeroes the work deposit for queries that never reach a
    replica (result-cache hits).  ``n_act`` (autoscaling): per-query
    active replica count (S, chunk); inactive replicas are masked out
    of the argmin — no new work — but their trackers keep draining,
    which is exactly the scale-in semantics (in-flight work finishes).
    ``up`` (fault injection): per-query replica-up mask (S, chunk, r);
    down replicas are masked out of the argmin exactly like inactive
    ones, and the step additionally reports whether the fault mask
    overrode the fault-free choice (``spill``) or left no candidate at
    all (``unavail``; the query then takes the fault-free choice — the
    dispatcher has nowhere better to send it).
    Returns ``(choice, work)`` — plus ``(spill, unavail)`` when ``up``
    is given — where choice is the (S, chunk) integer replica pick; the
    work state rides in the outer scan carry, so JSQ pressure persists
    across chunks; both the masked and the fused replicated paths
    consume the same choice stream.
    """
    faulty = up is not None

    def step(w, inp):
        if faulty:
            gap, svc, lv, upq = inp[:4]          # upq: (S, r)
            act = inp[4] if n_act is not None else None
        elif n_act is not None:
            gap, svc, lv, act = inp
        else:
            gap, svc, lv = inp                   # (S,), (S, p), (S,)
        w = jnp.maximum(w - gap[:, None, None], 0.0)
        backlog = jnp.max(w, axis=-1)            # (S, r) slowest server
        if n_act is not None:
            active = jnp.arange(r)[None, :] < act[:, None]
            backlog = jnp.where(active, backlog, jnp.inf)
        choice = jnp.argmin(backlog, axis=-1)    # (S,)
        if faulty:
            raw = choice
            bl_up = jnp.where(upq > 0, backlog, jnp.inf)
            any_up = jnp.any(jnp.isfinite(bl_up), axis=-1)
            choice = jnp.where(any_up, jnp.argmin(bl_up, axis=-1), raw)
            raw_up = jnp.take_along_axis(
                upq, raw[:, None], axis=-1)[:, 0] > 0
            out = (choice, any_up & ~raw_up, ~any_up)
        else:
            out = choice
        oh = (choice[:, None] == jnp.arange(r)[None, :]).astype(dtype)
        w = w + (oh * lv[:, None])[:, :, None] * svc[:, None, :]
        return w, out

    xs = (gaps.T, jnp.moveaxis(services, -1, 0), live.T)
    if faulty:
        xs = xs + (jnp.moveaxis(up.astype(jnp.int32), 1, 0),)
    if n_act is not None:
        xs = xs + (n_act.T,)
    w, out_seq = jax.lax.scan(step, w, xs)       # leaves: (chunk, S)
    if faulty:
        choice_seq, spill_seq, unav_seq = out_seq
        return choice_seq.T, w, spill_seq.T, unav_seq.T
    return out_seq.T, w


def _fcfs_segmented(arrivals: Array, services: Array, flags: Array,
                    carry_per_q: Optional[Array], impl: str,
                    stage: Optional[str] = None) -> Array:
    """FCFS completions of many queues packed as contiguous segments.

    The fused replicated engine compacts each chunk's queries into
    per-replica contiguous runs along the last axis; ``flags`` marks the
    first element of each run.  A segmented (max, +) scan then computes
    every queue's sample path in ONE pass over chunk elements — this is
    the kernel-level fusion that replaces r masked re-scans of the full
    stream.  ``carry_per_q`` holds each element's queue carry (the
    completion time of that queue's prior work), pre-composed at segment
    heads: seeding the head and resetting there is exactly seeding the
    whole segment.  ``impl`` picks `jax.lax.associative_scan` ("xla") or
    the Pallas segmented kernel ("pallas"; see `ops.interpret_mode`),
    named ``maxplus_segment_scan_<stage>`` after the queue level.
    """
    a = arrivals + services
    b = services
    flags = jnp.broadcast_to(flags, a.shape)
    if carry_per_q is not None:
        a = jnp.where(flags, jnp.maximum(a, carry_per_q + b), a)
    if impl == "pallas":
        from repro.kernels.maxplus_scan import ops as mp_ops
        out_a, _ = mp_ops.maxplus_segment_scan(
            a, b, flags, name=_kernel_name("maxplus_segment_scan", stage))
        return out_a
    from repro.kernels.maxplus_scan.ref import maxplus_segment_combine
    out_a, _, _ = jax.lax.associative_scan(
        maxplus_segment_combine, (a, b, flags), axis=-1)
    return out_a


def fcfs_completion_times_routed(
    arrivals: Array, services: Array, assign: Array, r: int,
    *, impl: str = "auto", carry: Optional[Array] = None,
) -> tuple[Array, Array]:
    """Completions of r parallel FCFS queues with per-query routing.

    arrivals: (..., n) nondecreasing; services: (..., n) positive;
    assign: (..., n) integers in [0, r) — each query joins the FCFS queue
    of its assigned replica, in arrival order.  carry: optional (..., r)
    completion time of each queue's prior work.

    Fused route-compaction (one scan over n elements instead of r masked
    re-scans over all n): stable-sort by assignment so each queue is a
    contiguous segment, seed segment heads from the carry, run one
    segmented (max, +) scan, and scatter completions back to arrival
    order.  Returns ``(completions (..., n), new_carry (..., r))`` where
    empty queues keep their old carry.
    """
    if impl == "auto":
        from repro.kernels.maxplus_scan.ops import resolve_scan_impl
        impl = resolve_scan_impl(impl)
    if r < 1:
        raise ValueError(f"need at least one queue; got r={r}")
    if carry is None:
        carry = jnp.full(assign.shape[:-1] + (r,), -jnp.inf,
                         arrivals.dtype)
    order = jnp.argsort(assign, axis=-1, stable=True)
    asg_s = jnp.take_along_axis(assign, order, axis=-1)
    flags = jnp.concatenate(
        [jnp.ones_like(asg_s[..., :1], dtype=bool),
         asg_s[..., 1:] != asg_s[..., :-1]], axis=-1)
    counts = jnp.sum(
        assign[..., None, :] == jnp.arange(r)[:, None], axis=-1)
    ends = jnp.clip(jnp.cumsum(counts, axis=-1) - 1, 0, None)
    arr_s = jnp.take_along_axis(arrivals, order, axis=-1)
    svc_s = jnp.take_along_axis(services, order, axis=-1)
    carry_q = jnp.take_along_axis(carry, asg_s, axis=-1)
    done_s = _fcfs_segmented(arr_s, svc_s, flags, carry_q, impl)
    new_carry = jnp.where(counts > 0,
                          jnp.take_along_axis(done_s, ends, axis=-1),
                          carry)
    inv = jnp.argsort(order, axis=-1, stable=True)
    return jnp.take_along_axis(done_s, inv, axis=-1), new_carry


@functools.partial(
    jax.jit, static_argnames=("n_queries", "p", "mode", "impl", "chunk",
                              "warmup_fraction", "hist_bins", "tap_size",
                              "r", "routing", "has_cache", "replica_impl",
                              "autoscale", "telemetry", "fault"))
def _simulate_stream(
    key: Array,
    proc: ArrivalProcess,
    params: ServerParams,
    cache_hit: Array,
    cache_service: Array,
    n_queries: int,
    p: int,
    mode: str,
    impl: str,
    chunk: int,
    warmup_fraction: float,
    hist_bins: int,
    tap_size: int = 0,
    r: int = 1,
    routing: str = "round_robin",
    has_cache: bool = False,
    replica_impl: str = "fused",
    autoscale: Optional[AutoscalePolicy] = None,
    telemetry: Optional[TelemetrySpec] = None,
    fault: Optional[FaultSpec] = None,
) -> SimResult:
    """The one chunked engine behind every fork-join entry point.

    ``r``/``routing``/``has_cache`` are static: the single-replica,
    no-cache compilation is EXACTLY the pre-replication program (same
    draws, same op order, bit-identical statistics).

    ``replica_impl`` selects the r > 1 engine: "fused" (default) runs the
    route-compacted path — each query scanned ONCE on its own replica's
    queues, ~r x less work — while "masked" keeps the original
    full-stream masked re-scans as a cross-check oracle.  Both consume
    the same routing choices and draws, so their sample paths agree
    query-for-query (exactly in exact arithmetic; see the equality tests
    in tests/test_replication.py).

    ``telemetry`` (static) turns on the per-time-bin accumulators of
    `repro.obs.timeline`.  It draws NO randomness and appends carry
    elements only when present, so ``telemetry=None`` is the
    bit-identical pre-telemetry program.  Timeline binning keys off an
    UNWRAPPED absolute clock carried alongside the period-wrapped
    ``t_origin`` (profiles wrap for rate lookups; telemetry must not).

    ``autoscale`` (static) makes the ACTIVE replica count time-varying
    inside [min_r, max_r] (callers provision r = max_r): the
    `repro.launch.elastic` controller scan runs per chunk on the
    carried feedback state, and the per-query active counts feed the
    routing policies.  Like telemetry it appends carry slots only when
    present — ``autoscale=None`` compiles the exact static-r program —
    and draws no randomness, so the canonical chunk plan is untouched.

    ``fault`` (static) injects the `repro.core.faults.FaultSpec`
    failure modes: per-query replica-up masks (deterministic windows +
    the MTBF/MTTR Markov process) flow into the routing policies as
    failover (down replicas get no new work; in-flight work drains,
    exactly the autoscale scale-in semantics), degraded-server factors
    rescale the canonical service draws, the broker timeout turns the
    join into a k-of-p order statistic, and hedged duplicates race the
    straggling join.  All fault randomness comes from the
    ``_FAULT_SALT`` stream and all fault carry slots append only when
    present, so ``fault=None`` compiles the bit-identical pre-fault
    program — and an all-up spec reproduces its statistics bitwise.

    Each stage of the chunk body runs under ``jax.named_scope(
    "stream.<stage>")`` (draws, fault, cache, autoscale, route, broker,
    server, join, stats, telemetry), which names its device operations in
    a profiler trace and changes nothing in the program.
    """
    spans.count("stream/traced")    # runs when the engine is traced
    n_scen = proc.rates.shape[0]
    elastic = autoscale is not None
    faulty = fault is not None
    # sub-features gate their ops individually so an all-up spec keeps
    # every branch (and the fused fast path) of the fault-free program
    f_outage = faulty and fault.has_outages
    f_quorum = faulty and fault.broker_timeout_seconds is not None
    f_hedge = faulty and fault.hedge_after_seconds is not None
    n_chunks = -(-n_queries // chunk)
    n_warm = int(n_queries * warmup_fraction)
    dtype = jnp.result_type(float)

    if telemetry is not None:
        tl_bins = telemetry.n_bins
        if telemetry.horizon_seconds is not None:
            tl_horizon = jnp.full((n_scen,), telemetry.horizon_seconds,
                                  dtype)
        else:
            tl_horizon = jnp.broadcast_to(
                n_queries / jnp.maximum(
                    proc.mean_rate.astype(dtype), 1e-30), (n_scen,))
        tl_bin_w = tl_horizon / tl_bins
        tl_slo = (jnp.inf if telemetry.slo_seconds is None
                  else telemetry.slo_seconds)

    s_broker = jnp.broadcast_to(
        jnp.asarray(params.s_broker, dtype), (n_scen,))
    cache_hit = jnp.broadcast_to(jnp.asarray(cache_hit, dtype), (n_scen,))
    cache_service = jnp.broadcast_to(
        jnp.asarray(cache_service, dtype), (n_scen,))

    # Per-scenario histogram scale off the Eq 7 analytic ballpark so the
    # fixed bin budget lands where each scenario's mass actually is.  The
    # dispatcher splits arrivals over r replicas (and the result cache
    # short-circuits hits), so the per-replica operating point is
    # lam * (1 - hit_r) / r; both factors are exact no-ops at the
    # default r=1, hit_r=0.
    ref_rate = jnp.broadcast_to(proc.mean_rate.astype(dtype), (n_scen,))
    if has_cache:
        ref_rate = ref_rate * (1.0 - cache_hit)
    s_mean = jnp.broadcast_to(
        jnp.asarray(service_time_server(params), dtype), (n_scen,))
    _, hi = queueing.response_time_bounds(ref_rate / r, params)
    hi = jnp.broadcast_to(jnp.asarray(hi, dtype), (n_scen,))
    scale = jnp.where(jnp.isfinite(hi) & (hi > 0), hi, 100.0 * s_mean)
    ln10 = math.log(10.0)
    hist_log_lo = jnp.log(scale) - _HIST_DECADES_BELOW * ln10
    hist_log_step = jnp.full((n_scen,),
                             _HIST_DECADES_TOTAL * ln10 / hist_bins, dtype)

    has_trace = proc.trace_gaps is not None
    if has_trace:
        gaps_full = jnp.asarray(proc.trace_gaps, dtype)[:n_queries]
        pad = n_chunks * chunk - n_queries
        gap_chunks = jnp.pad(gaps_full, (0, pad),
                             constant_values=1.0).reshape(n_chunks, chunk)
        xs = (jnp.arange(n_chunks), gap_chunks)
    else:
        xs = jnp.arange(n_chunks)

    rows = jnp.arange(n_scen)[:, None]
    col = jnp.arange(chunk)
    period = jnp.asarray(proc.period_seconds, dtype)

    # Max-plus maps are translation-invariant, so the carry is REBASED to
    # each chunk's origin: completion state is stored relative to the last
    # arrival, and only the (period-wrapped) absolute clock `t_origin` is
    # kept for profile lookups.  Clock magnitudes therefore stay O(chunk
    # duration) forever — float32 accuracy is independent of the simulated
    # horizon, which is what lets millions of queries stream through.
    #
    # Replicated carry: c_brk is (S, r), c_srv and the JSQ work tracker
    # are (S, r, p), the cache queue's carry is (S,).  Unused trackers
    # (non-JSQ routing, cache off) are carried as constants and dead-code
    # eliminated by XLA.
    def body(carry, x):
        (t_origin, c_brk, c_srv, c_cache, w_jsq, count, s_resp, ss_resp,
         s_br, s_cl, s_sv, hist, tap_pri, tap_val) = carry[:14]
        off = 14
        if elastic:
            as_carry = carry[off:off + 5]
            rep_secs, elapsed = carry[off + 5:off + 7]
            off += 7
        if faulty:
            (f_up, f_tabs, s_spill, s_unav, s_degr) = carry[off:off + 5]
            off += 5
        if telemetry is not None:
            (t_abs, tm_count, tm_resp, tm_bb, tm_bs, tm_rc, tm_hit,
             tm_slo) = carry[off:off + 8]
            toff = off + 8
            if elastic:
                tm_act = carry[toff]
                toff += 1
            if faulty:
                tm_up, tm_spill, tm_degr = carry[toff:toff + 3]
        if has_trace:
            c_idx, trace_gaps_c = x
        else:
            c_idx = x
        with _stage("draws"):
            u_gaps, u_brk, services = chunk_random_draws(
                key, c_idx, n_scen, chunk, p, params, mode,
                with_gaps=not has_trace)
            if has_trace:
                gaps = jnp.broadcast_to(trace_gaps_c[None, :],
                                        (n_scen, chunk)).astype(dtype)
            else:
                # the Sec 4.2 structure: homogeneous Poisson within the
                # chunk, at the profile rate read off at its start time
                rate = jnp.maximum(proc.rate_at(t_origin), 1e-30)
                gaps = u_gaps / rate[:, None]
            arrivals = jnp.cumsum(gaps, axis=-1)  # relative to chunk origin
            # the rebase shift below; captured BEFORE the fused branches
            # permute `arrivals` into replica-compacted layout
            last_arrival = arrivals[:, -1]
            gidx = c_idx * chunk + col
            s_broker_c = u_brk * s_broker[:, None]

        if faulty:
            with _stage("fault"):
                # Degraded servers: rescale the CANONICAL service draws (a
                # slow disk / throttled CPU on one index partition, on every
                # replica) before anything consumes them — the autoscaler's
                # demand feedback, telemetry's busy integrals and both
                # replica engines all see the degraded times.
                if fault.degraded:
                    factors = [1.0] * p
                    for srv, f in fault.degraded:
                        factors[srv % p] *= f
                    services = services * jnp.asarray(
                        factors, dtype)[None, :, None]
                # Replica-up mask at each arrival, off the chunking-invariant
                # recurrence; stochastic transitions draw from the salted
                # fault stream so the canonical plan is untouched.
                k_fault = jax.random.fold_in(
                    jax.random.fold_in(key, c_idx), _FAULT_SALT)
                u_fault = (jax.random.uniform(
                    jax.random.fold_in(k_fault, 0), (n_scen, chunk, r))
                    if fault.mtbf_seconds is not None else None)
                (f_up,), up_q = fault_scan(
                    fault, r, (f_up,), f_tabs[:, None] + arrivals, gaps,
                    u_fault)
                up_cnt = jnp.sum(up_q.astype(dtype), axis=-1)  # (S, chunk)
                f_tabs = f_tabs + last_arrival

        with _stage("cache"):
            if has_cache:
                # Result-cache hits short-circuit at their replica's broker
                # cache: an FCFS queue with Exp(s_cache) service, zero
                # index-server work — the Eq 8 topology (per-cluster cache),
                # so the analytic term at lam / r describes the same queue.
                kc = jax.random.fold_in(
                    jax.random.fold_in(key, c_idx), _CACHE_SALT)
                kh, ks = jax.random.split(kc)
                is_hit = jax.random.bernoulli(
                    kh, jnp.broadcast_to(cache_hit[:, None], (n_scen, chunk)))
                miss_f = 1.0 - is_hit.astype(dtype)
                t_cache = (jax.random.exponential(ks, (n_scen, chunk))
                           * cache_service[:, None]
                           * is_hit.astype(dtype))
            else:
                miss_f = None

        if elastic:
            with _stage("autoscale"):
                # Controller feedback in chunk (arrival) order, BEFORE any
                # routing permutation: each query's server-seconds of demand
                # (misses only — hits never reach the index servers) plus
                # the valid-query mask, so the padded tail advances neither
                # the decision clock nor the cost integral.
                vf = (gidx < n_queries).astype(dtype)[None, :]
                dem = jnp.sum(services, axis=1)
                if has_cache:
                    dem = dem * miss_f
                gaps_v = gaps * vf
                as_carry, n_act = autoscale_scan(
                    autoscale, p, as_carry, gaps_v, dem * vf,
                    up_frac=up_cnt / r if f_outage else None)
                n_act_f = n_act.astype(dtype)
                # the cost integral the policy sweeps price: provisioned
                # replica-seconds and wall seconds (warmup included — the
                # fleet is paid for from t=0)
                rep_secs = rep_secs + jnp.sum(n_act_f * gaps_v, axis=-1)
                elapsed = elapsed + jnp.sum(gaps_v, axis=-1)
        if telemetry is not None:
            with _stage("telemetry"):
                # chunk-order captures BEFORE the fused branches permute or
                # rescale anything: arrival offsets plus each query's
                # EFFECTIVE demand (cache hits never reach broker/servers,
                # so misses-only is the busy time conservation requires)
                tm_arr = arrivals
                tm_svc = (services * miss_f[:, None, :] if has_cache
                          else services)
                tm_brk = s_broker_c * miss_f if has_cache else s_broker_c
                tm_hit_c = is_hit.astype(dtype) if has_cache else None
        def _quorum_join(completions, fork_base, axis):
            """Fork-join merge: full quorum, or k-of-p past the timeout.

            The broker waits for all p servers until ``fork_base +
            broker_timeout_seconds``; past it, it returns as soon as at
            least k answers are in (the k-th order statistic of the
            per-server completions).  Returns ``(join, degraded)``;
            with no timeout configured this is exactly ``max`` and
            ``degraded`` is None.  An infinite timeout keeps the select
            on the full-quorum side everywhere, so the join is bitwise
            the fault-free one.
            """
            full = jnp.max(completions, axis=axis)
            if not f_quorum:
                return full, None
            k = fault.quorum(p)
            if k >= p:
                return full, jnp.zeros(full.shape, bool)
            t_k = jnp.take(jnp.sort(completions, axis=axis), k - 1,
                           axis=axis)
            deadline = fork_base + fault.broker_timeout_seconds
            late = full > deadline
            return jnp.where(late, jnp.maximum(t_k, deadline), full), late

        degr = None
        # `perm` maps chunk-order (S, chunk) arrays into the layout the
        # fused branches compute in (replica-compacted); None = identity.
        # All streaming statistics are permutation-invariant (sums,
        # histogram scatter-adds, the priority-reservoir tap), so the
        # epilogue only needs mf / priorities / is_hit permuted the same
        # way as the responses.
        perm = None
        if r == 1:
            # single replica: EXACTLY the pre-replication program (the
            # miss mask is the only difference, and only with a cache)
            if has_cache:
                with _stage("cache"):
                    s_broker_c = s_broker_c * miss_f
                    services = services * miss_f[:, None, :]
                    cache_done = fcfs_completion_times(
                        arrivals, t_cache, impl=impl, carry=c_cache[:, 0],
                        stage="cache")
                    c_cache_new = (cache_done[:, -1])[:, None]
            with _stage("broker"):
                broker_done = fcfs_completion_times(
                    arrivals, s_broker_c, impl=impl, carry=c_brk[:, 0],
                    stage="broker")
                c_brk_new = (broker_done[:, -1])[:, None]
            with _stage("server"):
                fork = jnp.broadcast_to(broker_done[:, None, :],
                                        (n_scen, p, chunk))
                completions = fcfs_completion_times(
                    fork, services, impl=impl, carry=c_srv[:, 0],
                    stage="server")
                c_srv_new = (completions[:, :, -1])[:, None, :]
            with _stage("join"):
                join, degr = _quorum_join(completions, broker_done, axis=1)
                server0 = completions[:, 0, :]
            w_jsq_new = w_jsq
        else:
            with _stage("route"):
                live = miss_f if has_cache else jnp.ones_like(gaps)
                up_route = up_q if f_outage else None
                assign, spill_q, unav_q = _routing_assign(
                    routing, r, key, c_idx, gidx, n_scen, chunk,
                    n_act=n_act if elastic else None, up=up_route)
                if assign is None:  # jsq: needs the carried work state
                    routed = _jsq_route(
                        w_jsq, gaps, services, live, r, dtype,
                        n_act=n_act if elastic else None, up=up_route)
                    if up_route is None:
                        assign, w_jsq_new = routed
                    else:
                        assign, w_jsq_new, spill_q, unav_q = routed
                else:
                    w_jsq_new = w_jsq

        if telemetry is not None and r > 1:
            tm_asg = assign          # replica of each chunk-order query

        if r == 1:
            pass
        elif replica_impl == "masked":
            # Reference oracle: every replica scans the FULL stream;
            # phantom (zero-service) entries cannot delay later real
            # queries (see module doc).  ~r x redundant work — kept for
            # the fused-vs-masked equality tests.
            with _stage("route"):
                mask = (assign[:, None, :]
                        == jnp.arange(r)[None, :, None]).astype(dtype)
                # hits occupy their replica's cache queue; only misses
                # enter its broker + index servers
                mask_srv = mask * miss_f[:, None, :] if has_cache else mask
                arr_r = jnp.broadcast_to(arrivals[:, None, :],
                                         (n_scen, r, chunk))
            if has_cache:
                with _stage("cache"):
                    cache_done_r = fcfs_completion_times(
                        arr_r, t_cache[:, None, :] * mask, impl=impl,
                        carry=c_cache, stage="cache")
                    cache_done = jnp.sum(cache_done_r * mask, axis=1)
                    c_cache_new = cache_done_r[:, :, -1]
            with _stage("broker"):
                broker_done_r = fcfs_completion_times(
                    arr_r, s_broker_c[:, None, :] * mask_srv, impl=impl,
                    carry=c_brk, stage="broker")
                c_brk_new = broker_done_r[:, :, -1]
            with _stage("server"):
                fork = jnp.broadcast_to(broker_done_r[:, :, None, :],
                                        (n_scen, r, p, chunk))
                completions = fcfs_completion_times(
                    fork, services[:, None, :, :] * mask_srv[:, :, None, :],
                    impl=impl, carry=c_srv, stage="server")
                c_srv_new = completions[:, :, :, -1]
            with _stage("join"):
                join_r, degr_r = _quorum_join(completions,
                                              broker_done_r, axis=2)
                # read each query off its OWN replica's sample path
                broker_done = jnp.sum(broker_done_r * mask_srv, axis=1)
                join = jnp.sum(join_r * mask_srv, axis=1)
                if f_quorum:
                    degr = jnp.sum(degr_r.astype(dtype) * mask_srv,
                                   axis=1) > 0.0
                server0 = jnp.sum(completions[:, :, 0, :] * mask_srv,
                                  axis=1)
        elif (routing == "round_robin" and chunk % r == 0
              and not elastic and not f_outage):
            # Fused fast path: with chunk % r == 0 the round-robin
            # assignment is col % r every chunk, so compaction into
            # per-replica contiguous runs is a pure reshape — no sort.
            # (Autoscaled round-robin wraps at the time-varying active
            # count, and failover spills break the col % r pattern, so
            # both ride the general sorted path below.)
            # Each query is scanned ONCE on its own replica's queues:
            # chunk broker elements + p * chunk server elements total,
            # r x less work than the masked oracle.
            ct = chunk // r

            def to_rep(x):                       # (S, chunk) -> (S, r, ct)
                return x.reshape(n_scen, ct, r).swapaxes(-1, -2)

            def perm(x):
                return to_rep(jnp.broadcast_to(x, (n_scen, chunk))
                              ).reshape(n_scen, chunk)

            with _stage("route"):
                arr_q = to_rep(arrivals)
                svc_q = services.reshape(n_scen, p, ct, r).transpose(
                    0, 3, 1, 2)
                brk_q = to_rep(s_broker_c)
            if has_cache:
                with _stage("cache"):
                    miss_q = to_rep(miss_f)
                    brk_q = brk_q * miss_q
                    svc_q = svc_q * miss_q[:, :, None, :]
                    cache_done_q = fcfs_completion_times(
                        arr_q, to_rep(t_cache), impl=impl, carry=c_cache,
                        stage="cache")
                    cache_done = cache_done_q.reshape(n_scen, chunk)
                    c_cache_new = cache_done_q[..., -1]
            with _stage("broker"):
                broker_done_q = fcfs_completion_times(
                    arr_q, brk_q, impl=impl, carry=c_brk, stage="broker")
                c_brk_new = broker_done_q[..., -1]
            with _stage("server"):
                fork = jnp.broadcast_to(broker_done_q[:, :, None, :],
                                        (n_scen, r, p, ct))
                completions = fcfs_completion_times(
                    fork, svc_q, impl=impl, carry=c_srv, stage="server")
                c_srv_new = completions[..., -1]
            with _stage("join"):
                broker_done = broker_done_q.reshape(n_scen, chunk)
                join_q, degr_q = _quorum_join(completions,
                                              broker_done_q, axis=2)
                join = join_q.reshape(n_scen, chunk)
                if f_quorum:
                    degr = degr_q.reshape(n_scen, chunk)
                server0 = completions[:, :, 0, :].reshape(n_scen, chunk)
                arrivals = arr_q.reshape(n_scen, chunk)
        else:
            # Fused general path (random, jsq, uneven round-robin):
            # stable-sort by replica so each replica's queries form a
            # contiguous segment, seed segment heads from the carries,
            # and run ONE segmented (max, +) scan per queue level.
            # Stable sort preserves arrival order within a replica, so
            # each segment IS that replica's FCFS arrival sequence.
            with _stage("route"):
                order = jnp.argsort(assign, axis=-1, stable=True)
                asg_s = jnp.take_along_axis(assign, order, axis=-1)
                flags = jnp.concatenate(
                    [jnp.ones_like(asg_s[:, :1], dtype=bool),
                     asg_s[:, 1:] != asg_s[:, :-1]], axis=-1)
                counts = jnp.sum(
                    assign[:, None, :] == jnp.arange(r)[None, :, None],
                    axis=-1)                              # (S, r)
                ends = jnp.clip(jnp.cumsum(counts, axis=-1) - 1, 0, None)

                def perm(x):
                    return jnp.take_along_axis(
                        jnp.broadcast_to(x, (n_scen, chunk)), order,
                        axis=-1)

                arrivals = perm(arrivals)
                svc_s = jnp.take_along_axis(services, order[:, None, :],
                                            axis=-1)
                brk_s = perm(s_broker_c)
            if has_cache:
                with _stage("cache"):
                    miss_s = perm(miss_f)
                    brk_s = brk_s * miss_s
                    svc_s = svc_s * miss_s[:, None, :]
                    cache_done = _fcfs_segmented(
                        arrivals, perm(t_cache), flags,
                        jnp.take_along_axis(c_cache, asg_s, axis=-1), impl,
                        stage="cache")
                    c_cache_new = jnp.where(
                        counts > 0,
                        jnp.take_along_axis(cache_done, ends, axis=-1),
                        c_cache)
            with _stage("broker"):
                broker_done = _fcfs_segmented(
                    arrivals, brk_s, flags,
                    jnp.take_along_axis(c_brk, asg_s, axis=-1), impl,
                    stage="broker")
                c_brk_new = jnp.where(
                    counts > 0,
                    jnp.take_along_axis(broker_done, ends, axis=-1), c_brk)
            with _stage("server"):
                fork = jnp.broadcast_to(broker_done[:, None, :],
                                        (n_scen, p, chunk))
                carry_srv_q = jnp.take_along_axis(
                    jnp.swapaxes(c_srv, 1, 2), asg_s[:, None, :], axis=-1)
                completions = _fcfs_segmented(
                    fork, svc_s, flags[:, None, :], carry_srv_q, impl,
                    stage="server")
                srv_ends = jnp.take_along_axis(
                    completions, ends[:, None, :], axis=-1)   # (S, p, r)
                c_srv_new = jnp.where(counts[:, :, None] > 0,
                                      jnp.swapaxes(srv_ends, 1, 2), c_srv)
            with _stage("join"):
                join, degr = _quorum_join(completions, broker_done, axis=1)
                server0 = completions[:, 0, :]

        with _stage("join"):
            if f_hedge:
                # Hedged retries: each attempt races the (possibly partial-
                # quorum) join with a duplicate fork fired a backoff delay
                # after the broker fork, served OFF-QUEUE by spare capacity
                # with fresh draws from the salted fault stream (optimistic:
                # duplicates add no queue load — the trade Eq 6's
                # `hedge_threshold` prices).  A response the hedge wins is a
                # full-quorum result, so it clears the degraded flag.
                cand = None
                for h_j, h_delay in enumerate(fault.hedge_delays()):
                    k_h = jax.random.fold_in(k_fault, 1 + h_j)
                    dup = jnp.max(jax.random.exponential(
                        k_h, (n_scen, p, chunk)), axis=1) * s_mean[:, None]
                    if perm is not None:
                        dup = perm(dup)
                    c = broker_done + h_delay + dup
                    cand = c if cand is None else jnp.minimum(cand, c)
                if degr is not None:
                    degr = degr & (join <= cand)
                join = jnp.minimum(join, cand)

            if has_cache:
                if perm is not None:
                    is_hit = perm(is_hit)
                if degr is not None:
                    degr = degr & ~is_hit   # hits never fork: always whole
                resp_cache = cache_done - arrivals
                response = jnp.where(is_hit, resp_cache, join - arrivals)
                broker_res = jnp.where(is_hit, resp_cache,
                                       broker_done - arrivals)
                cluster_res = jnp.where(is_hit, 0.0, join - broker_done)
                server_res = jnp.where(is_hit, 0.0, server0 - broker_done)
            else:
                response = join - arrivals
                broker_res = broker_done - arrivals
                cluster_res = join - broker_done
                server_res = server0 - broker_done
                c_cache_new = c_cache
        with _stage("stats"):
            mf = ((gidx >= n_warm) & (gidx < n_queries)).astype(dtype)[None, :]
            mf0 = mf                 # chunk-order copy for chunk-order sums
            if perm is not None:
                mf = perm(mf)
            count = count + jnp.broadcast_to(jnp.sum(mf, -1), (n_scen,))
            s_resp = s_resp + jnp.sum(response * mf, -1)
            ss_resp = ss_resp + jnp.sum(response * response * mf, -1)
            s_br = s_br + jnp.sum(broker_res * mf, -1)
            s_cl = s_cl + jnp.sum(cluster_res * mf, -1)
            s_sv = s_sv + jnp.sum(server_res * mf, -1)
            if faulty:
                # spill/unavail live in chunk (arrival) order, the degraded
                # flag in the engine's (possibly permuted) layout; the sums
                # are permutation-invariant either way.
                if f_outage and r > 1:
                    s_spill = s_spill + jnp.sum(
                        spill_q.astype(dtype) * mf0, -1)
                    s_unav = s_unav + jnp.sum(
                        unav_q.astype(dtype) * mf0, -1)
                elif f_outage:       # r == 1: down means nowhere to route
                    s_unav = s_unav + jnp.sum(
                        (1.0 - up_q[:, :, 0].astype(dtype)) * mf0, -1)
                if degr is not None:
                    s_degr = s_degr + jnp.sum(degr.astype(dtype) * mf, -1)

            bins = jnp.clip(
                jnp.floor((jnp.log(jnp.maximum(response, 1e-30))
                           - hist_log_lo[:, None]) / hist_log_step[:, None]),
                0, hist_bins - 1).astype(jnp.int32)
            hist = hist.at[rows, bins].add(
                jnp.broadcast_to(mf, (n_scen, chunk)))

            if tap_size > 0:
                # Reservoir via random priorities (A-Res with equal weights):
                # every valid query gets an iid U(0,1) priority and the tap
                # keeps the tap_size largest seen so far — a uniform sample
                # without replacement, one top_k per chunk, O(tap) state.
                k_tap = jax.random.fold_in(
                    jax.random.fold_in(key, c_idx), _TAP_SALT)
                pri = jax.random.uniform(k_tap, (n_scen, chunk), dtype)
                if perm is not None:
                    pri = perm(pri)
                pri = jnp.where(mf > 0, pri, -jnp.inf)
                cat_pri = jnp.concatenate([tap_pri, pri], axis=-1)
                cat_val = jnp.concatenate(
                    [tap_val, jnp.broadcast_to(response, (n_scen, chunk))],
                    axis=-1)
                tap_pri, idx = jax.lax.top_k(cat_pri, tap_size)
                tap_val = jnp.take_along_axis(cat_val, idx, axis=-1)

        if telemetry is not None:
            with _stage("telemetry"):
                # Timeline tallies (no RNG, so the canonical draw plan is
                # untouched).  Bin by arrival time on the UNWRAPPED absolute
                # clock; warmup is included by design (transients are the
                # signal), only the tail padding is excluded.  Arrivals are
                # nondecreasing within a chunk, so each bin is a CONTIGUOUS
                # run of queries: per-bin sums are differences of one
                # prefix sum read at the bin-edge positions (vmapped
                # searchsorted) — O(chunk) per channel, an order of
                # magnitude cheaper than scatter-adds or one-hot
                # contractions inside the scan, and the per-chunk total
                # telescopes exactly (conservation is bit-exact).
                t_arr = t_abs[:, None] + tm_arr          # (S, chunk), sorted
                # padded tail queries (gidx >= n_queries) are a SUFFIX of
                # the sorted chunk, so clamping the bin-edge positions at
                # n_valid excludes them for free — no valid-mask multiply
                # on any channel
                n_valid = jnp.clip(n_queries - c_idx * chunk, 0, chunk)
                edges = tl_bin_w[:, None] * jnp.arange(
                    tl_bins, dtype=dtype)[None, :]        # (S, B)
                pos = jax.vmap(jnp.searchsorted)(t_arr, edges)
                pos = jnp.minimum(
                    jnp.concatenate(
                        [pos, jnp.full((n_scen, 1), chunk, pos.dtype)],
                        axis=-1),
                    n_valid)                              # (S, B + 1)

                # Two-level prefix sums: a full cumsum over the chunk is
                # multi-pass under XLA, but prefixes are only ever READ at
                # the B + 1 edge positions.  So: one pass of per-block
                # partial sums, a tiny cumsum over the ~chunk/blk blocks,
                # and a masked intra-block sum just at the edges — ~one
                # read of the data per channel instead of a scan.
                blk = 1
                while (blk < 128 and chunk % (blk * 2) == 0
                       and blk * (tl_bins + 1) < chunk):
                    blk *= 2
                nb = chunk // blk
                e_blk = pos // blk                        # (S, B + 1)
                e_within = pos - e_blk * blk
                e_blk_c = jnp.minimum(e_blk, nb - 1)
                e_within = jnp.where(e_blk > e_blk_c, blk, e_within)
                intra_mask = (jnp.arange(blk) < e_within[..., None]
                              ).astype(dtype)             # (S, B + 1, blk)

                def bin_sums(w):
                    """(S, ..., chunk) weights -> (S, ..., B) per-bin sums."""
                    lead = (1,) * (w.ndim - 2)
                    wb = w.reshape(w.shape[:-1] + (nb, blk))
                    blocks = jnp.cumsum(jnp.sum(wb, axis=-1), axis=-1)
                    eb = jnp.broadcast_to(
                        e_blk_c.reshape((n_scen,) + lead + (tl_bins + 1,)),
                        w.shape[:-1] + (tl_bins + 1,))
                    pre = jnp.where(
                        eb > 0,
                        jnp.take_along_axis(blocks, jnp.maximum(eb - 1, 0),
                                            axis=-1),
                        jnp.zeros_like(blocks[..., :1]))
                    wsel = jnp.take_along_axis(wb, eb[..., None], axis=-2)
                    take = pre + jnp.sum(
                        wsel * intra_mask.reshape(
                            (n_scen,) + lead + (tl_bins + 1, blk)),
                        axis=-1)
                    return take[..., 1:] - take[..., :-1]

                # counts need no cumsum at all: bins are contiguous runs, so
                # the per-bin count IS the difference of the edge positions
                cnt_inc = (pos[:, 1:] - pos[:, :-1]).astype(dtype)  # (S, B)
                tm_count = tm_count + cnt_inc
                if r == 1:
                    # single replica: every per-replica channel collapses to
                    # the plain one — skip the assignment mask entirely
                    tm_rc = tm_rc + cnt_inc[:, :, None]
                    tm_bb = tm_bb + bin_sums(tm_brk)[:, :, None]
                    tm_bs = tm_bs + jnp.moveaxis(
                        bin_sums(tm_svc), -1, 1)[:, :, None, :]
                else:
                    mask_a = (tm_asg[:, None, :]
                              == jnp.arange(r, dtype=jnp.int32)[None, :, None]
                              ).astype(dtype)             # (S, r, chunk)
                    tm_rc = tm_rc + jnp.swapaxes(bin_sums(mask_a), 1, 2)
                    tm_bb = tm_bb + jnp.swapaxes(
                        bin_sums(mask_a * tm_brk[:, None, :]), 1, 2)
                    tm_bs = tm_bs + jnp.moveaxis(
                        bin_sums(mask_a[:, :, None, :]
                                 * tm_svc[:, None, :, :]),
                        -1, 1)                            # (S, B, r, p)
                if has_cache:
                    tm_hit = tm_hit + bin_sums(tm_hit_c)
                # response-side tallies live in the engine's layout — bring
                # them BACK to (sorted) chunk order via the inverse permute
                if perm is not None:
                    inv = jnp.argsort(
                        perm(jnp.arange(chunk, dtype=jnp.int32)), axis=-1)
                    resp_c = jnp.take_along_axis(
                        jnp.broadcast_to(response, (n_scen, chunk)), inv,
                        axis=-1)
                else:
                    resp_c = response
                tm_resp = tm_resp + bin_sums(resp_c)
                tm_slo = tm_slo + bin_sums((resp_c > tl_slo).astype(dtype))
                if elastic:
                    # the autoscaler trajectory: active fleet size summed
                    # over each bin's arrivals (n_act is in chunk order)
                    tm_act = tm_act + bin_sums(n_act_f)
                if faulty:
                    # fault trajectory: surviving-replica count and spills
                    # are in chunk order; the degraded flag rides the same
                    # inverse permute as the responses
                    tm_up = tm_up + bin_sums(up_cnt)
                    if f_outage and r > 1:
                        tm_spill = tm_spill + bin_sums(spill_q.astype(dtype))
                    if degr is not None:
                        dg = jnp.broadcast_to(degr.astype(dtype),
                                              (n_scen, chunk))
                        if perm is not None:
                            dg = jnp.take_along_axis(dg, inv, axis=-1)
                        tm_degr = tm_degr + bin_sums(dg)
                t_abs = t_abs + last_arrival

        shift = last_arrival
        c_brk_s = c_brk_new - shift[:, None]
        c_srv_s = c_srv_new - shift[:, None, None]
        c_cache_s = (c_cache_new - shift[:, None] if has_cache
                     else c_cache_new)
        if elastic or f_outage:
            # An inactive (or failed) replica receives no work, so its
            # rebased carry would drift toward -inf chunk after chunk.
            # Clamping at the chunk origin is EXACT — seeding
            # max(a, c + b) is unchanged for any c <= the segment head's
            # arrival, and arrivals are positive — and pins a fully
            # drained replica at 0, the same cold state a scale-out (or
            # repaired) replica starts from.
            c_brk_s = jnp.maximum(c_brk_s, 0.0)
            c_srv_s = jnp.maximum(c_srv_s, 0.0)
            if has_cache:
                c_cache_s = jnp.maximum(c_cache_s, 0.0)
        new_carry = ((t_origin + shift) % period,
                     c_brk_s, c_srv_s, c_cache_s,
                     w_jsq_new,
                     count, s_resp, ss_resp, s_br, s_cl, s_sv, hist,
                     tap_pri, tap_val)
        if elastic:
            new_carry = new_carry + tuple(as_carry) + (rep_secs, elapsed)
        if faulty:
            new_carry = new_carry + (f_up, f_tabs, s_spill, s_unav,
                                     s_degr)
        if telemetry is not None:
            new_carry = new_carry + (t_abs, tm_count, tm_resp, tm_bb,
                                     tm_bs, tm_rc, tm_hit, tm_slo)
            if elastic:
                new_carry = new_carry + (tm_act,)
            if faulty:
                new_carry = new_carry + (tm_up, tm_spill, tm_degr)
        return new_carry, None

    zeros = jnp.zeros((n_scen,), dtype)
    init = (zeros, jnp.zeros((n_scen, r), dtype),
            jnp.zeros((n_scen, r, p), dtype),
            jnp.zeros((n_scen, r), dtype),
            jnp.zeros((n_scen, r, p), dtype),
            zeros, zeros,
            zeros, zeros, zeros, zeros,
            jnp.zeros((n_scen, hist_bins), dtype),
            jnp.full((n_scen, tap_size), -jnp.inf, dtype),
            jnp.full((n_scen, tap_size), jnp.nan, dtype))
    if elastic:
        init = init + autoscale_init(autoscale, n_scen, dtype) \
            + (zeros, zeros)
    if faulty:
        init = init + fault_init(fault, n_scen, r) \
            + (zeros, zeros, zeros, zeros)
    if telemetry is not None:
        zb = jnp.zeros((n_scen, tl_bins), dtype)
        init = init + (zeros, zb, zb,
                       jnp.zeros((n_scen, tl_bins, r), dtype),
                       jnp.zeros((n_scen, tl_bins, r, p), dtype),
                       jnp.zeros((n_scen, tl_bins, r), dtype),
                       zb, zb)
        if elastic:
            init = init + (zb,)
        if faulty:
            init = init + (zb, zb, zb)
    final, _ = jax.lax.scan(body, init, xs)
    (t_last, c_brk, c_srv, c_cache, w_jsq, count, s_resp, ss_resp, s_br,
     s_cl, s_sv, hist, tap_pri, tap_val) = final[:14]
    off = 14
    rep_secs = elapsed = None
    if elastic:
        rep_secs, elapsed = final[off + 5:off + 7]
        off += 7
    spill = unavail = degraded = None
    if faulty:
        spill, unavail, degraded = final[off + 2:off + 5]
        off += 5

    timeline = None
    if telemetry is not None:
        (_, tm_count, tm_resp, tm_bb, tm_bs, tm_rc, tm_hit,
         tm_slo) = final[off:off + 8]
        toff = off + 8
        active_sum = None
        if elastic:
            active_sum = final[toff]
            toff += 1
        up_sum = spill_sum = degraded_sum = None
        if faulty:
            up_sum, spill_sum, degraded_sum = final[toff:toff + 3]
        timeline = Timeline(
            bin_seconds=tl_bin_w, count=tm_count, resp_sum=tm_resp,
            busy_broker=tm_bb, busy_server=tm_bs, replica_count=tm_rc,
            hit_count=tm_hit, slo_count=tm_slo,
            active_sum=active_sum, up_sum=up_sum, spill_sum=spill_sum,
            degraded_sum=degraded_sum)

    return SimResult(
        count=count, sum_response=s_resp, sumsq_response=ss_resp,
        sum_broker=s_br, sum_cluster=s_cl, sum_server=s_sv,
        hist=hist, hist_log_lo=hist_log_lo, hist_log_step=hist_log_step,
        tap_response=tap_val, timeline=timeline,
        replica_seconds=rep_secs, elapsed_seconds=elapsed,
        spill_count=spill, unavail_count=unavail,
        degraded_count=degraded)


def _cache_args(result_cache) -> tuple[Array, Array, bool]:
    """Normalize ``result_cache=(hit_r, s_cache)`` into engine inputs."""
    if result_cache is None:
        return jnp.asarray(0.0), jnp.asarray(0.0), False
    hit_r, s_cache = result_cache
    return jnp.asarray(hit_r), jnp.asarray(s_cache), True


def simulate_fork_join(
    key: Array,
    lam: Union[float, ArrivalProcess],
    n_queries: int,
    params: ServerParams,
    *,
    p: Optional[int] = None,
    mode: str = "exponential",
    impl: str = "auto",
    warmup_fraction: float = 0.1,
    chunk_size: int = DEFAULT_CHUNK,
    hist_bins: int = DEFAULT_HIST_BINS,
    tap_size: int = 0,
    cluster: Optional[ClusterSpec] = None,
    r: Optional[int] = None,
    routing: Optional[str] = None,
    result_cache: Optional[tuple[float, float]] = None,
    replica_impl: Optional[str] = None,
    telemetry: Optional[TelemetrySpec] = None,
) -> SimResult:
    """Simulate the full broker + p-server fork-join network (Fig 8).

    The broker is visited once per query with service S_broker (the paper
    lumps broadcast+merge); its completions are the fork times.  Each index
    server runs an independent FCFS queue over the forked stream, and the
    join waits for the slowest server.  ``lam`` is either a constant rate
    in qps or any :class:`ArrivalProcess` (diurnal profile, trace replay).
    Streams through ``chunk_size`` query chunks; warmup queries are
    discarded from the returned streaming statistics.  ``tap_size > 0``
    additionally carries a bounded reservoir sample of per-query response
    times (see :class:`SimResult`).

    Topology rides ONE static argument, ``cluster=ClusterSpec(...)``:

    * ``r > 1`` grows the network to the replicated topology (Sec 6): a
      front-end dispatcher routes each query to one of ``r`` full
      replicas under ``routing`` ("round_robin" | "random" | "jsq");
      ``lam`` stays the TOTAL arrival rate.
    * ``result_cache=(hit_r, s_cache)`` adds the broker-level result
      cache of Eq 8: hits are served by their routed replica's
      broker-cache FCFS queue with mean service ``s_cache`` and never
      fork to its index servers.
    * ``replica_impl`` picks the replicated engine ("fused" default;
      "masked" is the re-scan oracle — see :func:`_simulate_stream`).
    * ``autoscale=AutoscalePolicy(...)`` makes the active replica count
      time-varying; the result gains ``replica_seconds`` /
      ``elapsed_seconds`` and (with telemetry) the active-replica
      trajectory.
    * ``fault=FaultSpec(...)`` injects replica outages (failover spills
      to survivors), degraded servers, a partial-quorum broker timeout
      and hedged retries; the result gains ``spill_count`` /
      ``unavail_count`` / ``degraded_count`` and (with telemetry) the
      up/spill/degraded trajectories.  See `repro.core.faults`.

    The loose keywords ``r=`` / ``routing=`` / ``result_cache=`` /
    ``replica_impl=`` are DEPRECATED shims for the same fields (warn
    once; see `repro.core.cluster.resolve_cluster`).

    ``telemetry=TelemetrySpec(...)`` additionally streams the per-time-
    bin `repro.obs.timeline.Timeline` onto the result (None, the
    default, is the bit-identical pre-telemetry program).
    """
    spec = resolve_cluster(cluster, r=r, routing=routing,
                           result_cache=result_cache,
                           replica_impl=replica_impl,
                           caller="simulate_fork_join")
    from repro.kernels.maxplus_scan.ops import resolve_scan_impl
    impl = resolve_scan_impl(impl)  # concrete before the jit cache key
    p = int(params.p) if p is None else p  # static before tracing
    chunk = max(1, min(chunk_size, n_queries))
    if isinstance(lam, ArrivalProcess):
        lam = _as_batch_process(lam)
        _check_trace(lam, n_queries)
        chunk = _clamp_chunk_for_profile(lam, chunk)
    # a constant rate is a stationary process, which the clamp leaves be;
    # the cache's values are traced, the rest of the topology static
    return _simulate_one(key, lam, params, spec.result_cache,
                         n_queries=n_queries, p=p, mode=mode, impl=impl,
                         chunk=chunk, warmup_fraction=warmup_fraction,
                         hist_bins=hist_bins, tap_size=tap_size,
                         spec=dataclasses.replace(spec, result_cache=None),
                         telemetry=telemetry)


@functools.partial(
    jax.jit, static_argnames=("n_queries", "p", "mode", "impl", "chunk",
                              "warmup_fraction", "hist_bins", "tap_size",
                              "spec", "telemetry"))
def _simulate_one(key, lam, params, result_cache, *, n_queries, p, mode,
                  impl, chunk, warmup_fraction, hist_bins, tap_size, spec,
                  telemetry):
    """`simulate_fork_join` past its Python-level checks, as one program:
    the inputs promoted to a batch of one scenario, the engine, and the
    batch axis taken off the result (run eagerly, the promotions and the
    slices are some 30 launches and transfers a call)."""
    cache_hit, cache_service, has_cache = _cache_args(result_cache)
    res = _simulate_stream(key, _as_batch_process(lam), _vec_params(params),
                           cache_hit, cache_service, n_queries, p,
                           mode, impl, chunk, warmup_fraction, hist_bins,
                           tap_size, r=spec.engine_r, routing=spec.routing,
                           has_cache=has_cache,
                           replica_impl=spec.replica_impl,
                           autoscale=spec.autoscale, telemetry=telemetry,
                           fault=spec.fault)
    return jax.tree_util.tree_map(lambda x: x[0], res)


def simulate_fork_join_batch(
    key: Array,
    lam: Union[Array, ArrivalProcess],
    params: ServerParams,
    n_queries: int,
    *,
    p: int,
    mode: str = "exponential",
    impl: str = "auto",
    warmup_fraction: float = 0.1,
    chunk_size: int = DEFAULT_CHUNK,
    hist_bins: int = DEFAULT_HIST_BINS,
    tap_size: int = 0,
    cluster: Optional[ClusterSpec] = None,
    r: Optional[int] = None,
    routing: Optional[str] = None,
    result_cache: Optional[tuple[float, float]] = None,
    replica_impl: Optional[str] = None,
    telemetry: Optional[TelemetrySpec] = None,
) -> SimResult:
    """S fork-join scenarios in one XLA program; all stats are (S,).

    ``lam`` is an (S,) rate vector or an :class:`ArrivalProcess` with
    (S, n_bins) rates; every ``params`` field is (S,).  All scenarios
    share the SAME static topology ``cluster=ClusterSpec(...)`` and
    server count ``p`` (grids over p, r or autoscale policies dispatch
    one batch per distinct static config — see `repro.core.sweep`); the
    loose ``r=`` / ``routing=`` / ``result_cache=`` / ``replica_impl=``
    keywords are the deprecated shim.  With ``impl="pallas"`` the
    per-chunk (S, r, p, chunk) and (S, r, chunk) FCFS recurrences
    flatten onto the row axis of `maxplus_scan`, so all S * r * (p + 1)
    sample paths run as a single Pallas grid.

    Peak memory of the fused replicated engine is S * p * chunk_size
    floats — independent of ``n_queries`` AND of ``r`` (each query is
    scanned once, on its own replica); only the carries grow with r, at
    S * r * p scalars.  The "masked" oracle keeps the original
    S * r * p * chunk_size law.
    """
    spec = resolve_cluster(cluster, r=r, routing=routing,
                           result_cache=result_cache,
                           replica_impl=replica_impl,
                           caller="simulate_fork_join_batch")
    from repro.kernels.maxplus_scan.ops import resolve_scan_impl
    impl = resolve_scan_impl(impl)  # concrete before the jit cache key
    cache_hit, cache_service, has_cache = _cache_args(spec.result_cache)
    proc = _as_batch_process(lam)
    _check_trace(proc, n_queries)
    chunk = _clamp_chunk_for_profile(
        proc, max(1, min(chunk_size, n_queries)))
    return _simulate_stream(key, proc, params, cache_hit, cache_service,
                            n_queries, p, mode, impl,
                            chunk, warmup_fraction, hist_bins, tap_size,
                            r=spec.engine_r, routing=spec.routing,
                            has_cache=has_cache,
                            replica_impl=spec.replica_impl,
                            autoscale=spec.autoscale, telemetry=telemetry,
                            fault=spec.fault)


@functools.partial(jax.jit, static_argnames=("c",))
def simulate_mmc(arrivals: Array, services: Array, c: int) -> Array:
    """M/M/c FCFS via the Kiefer-Wolfowitz workload-vector recursion.

    State w = sorted vector of the c servers' remaining work at an arrival.
    On arrival i: start delay = w[0]; after assigning service S_i to the
    least-loaded server and advancing time by the next interarrival gap:

        w' = sort( (w + S_i e_1) - gap )_+

    Supports the paper's stated future work (multi-threaded index servers).
    Returns response times (delay + own service).
    """
    gaps = jnp.diff(arrivals, prepend=arrivals[:1] * 0.0)

    def step(w, inp):
        gap, s = inp
        w = jnp.maximum(w - gap, 0.0)          # advance to this arrival
        delay = w[0]
        w = w.at[0].add(s)                     # assign to least loaded
        w = jnp.sort(w)
        return w, delay + s

    _, resp = jax.lax.scan(step, jnp.zeros((c,), services.dtype),
                           (gaps, services))
    return resp
