"""The reference's answer to an N+k what-if call on a tail-tolerant cluster:
``plan_capacity(survive_faults=k)`` where every replica's broker cuts the
join short past a timeout and hedges its stragglers.

The cluster is ``cluster.py``'s (round-robin replicas of a broker FCFS
queue and p index-server FCFS queues, no result cache), with three
mechanisms added, after Dean and Barroso, "The Tail at Scale" (CACM 56(2),
2013):

* failover: replicas ``0 .. k-1`` are down for the whole run, and the
  dispatcher splits the stream evenly over the survivors: query g (counted
  from 0 over the run) goes to the (g mod n_up)-th surviving replica in
  index order;
* a partial quorum: the broker waits for all p answers until ``timeout``
  after its fork (its own completion); past that it answers as soon as
  ``quorum_k`` are in, at max(k-th earliest answer, fork + timeout).  Such
  an answer is degraded (it misses partitions);
* hedging: attempt h (from 0) fires a duplicate of the whole fork
  ``delays[h]`` after the fork, served off-queue by spare capacity: it
  answers at fork + delay + the largest of p fresh exponential service
  times, and the query takes whichever comes first.  An answer the hedge
  gives is whole, so it clears the degraded flag.

The hedge's service times are the planner's own random numbers:
``fold_in(fold_in(fold_in(key, c), FAULT_SALT), 1 + h)`` for chunk c gives
unit exponentials of shape (1, p, chunk), and the duplicate's time is their
largest over p times the mean server time.

The N+k loop: the Section 6 sizing gives n replicas; the fleet of n + k is
simulated with k down and, while the faulted 95th percentile misses the
SLO, grown by 1, 2, 4, ... replicas until it holds (or reaches
``REPLICA_CAP``), then bisected down to the smallest fleet that holds.
Then the returned fleet is simulated with every replica up, as the
planner's fault-free cross-check.  Both runs use the same key, so the same draws.

Statistics as in ``cluster.py``; the histogram's edges are set at the
provisioned count of replicas, down ones included, as the planner's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import analytic, cluster, deployment, draws

QUANTILE = 0.95
# the salt of the planner's stream of fault draws
FAULT_SALT = 0xFA17
# the largest fleet the planner simulates
REPLICA_CAP = 256


@functools.partial(jax.jit, static_argnames=("chunk", "p", "attempts"))
def _hedge_chunk(key, c, *, chunk, p, attempts):
    k_fault = jax.random.fold_in(jax.random.fold_in(key, c), FAULT_SALT)
    return jnp.stack([
        jnp.max(jax.random.exponential(jax.random.fold_in(k_fault, 1 + h),
                                       (1, p, chunk)), axis=1)[0]
        for h in range(attempts)])


def hedge_draws(key, c: int, *, chunk: int, p: int, attempts: int):
    """(attempts, chunk) host copies of chunk ``c``'s largest-of-p unit
    exponentials, one row per hedge attempt."""
    return np.asarray(_hedge_chunk(key, jnp.int32(c), chunk=chunk, p=p,
                                   attempts=attempts))


def fault_of(config: dict) -> dict:
    """The configuration's ``fault`` group in seconds: the broker timeout,
    the quorum and the hedges' fire times after the fork (each attempt
    twice as late again as the last, the planner's default backoff)."""
    f = config["fault"]
    base = f["hedge_after_ms"] * deployment.MS
    delays, t = [], 0.0
    for h in range(int(f["hedge_attempts"])):
        t += base * 2.0 ** h
        delays.append(t)
    return {"timeout": f["broker_timeout_ms"] * deployment.MS,
            "quorum_k": int(f["quorum_k"]), "delays": delays}


def join(s_done, fork, dup, *, timeout, quorum_k, delays, dtype):
    """(answer time, degraded) of each query from its p servers'
    completions ``s_done`` (p, m), its fork times (m,) and its hedges'
    unit draws ``dup`` (attempts, m), already times the server time."""
    dt = np.dtype(dtype)
    full = s_done.max(axis=0)
    answer, degraded = full, np.zeros(full.shape, bool)
    p = s_done.shape[0]
    k = min(max(quorum_k, 1), p)
    if timeout is not None and k < p:
        t_k = np.sort(s_done, axis=0)[k - 1]
        deadline = fork + dt.type(timeout)
        degraded = full > deadline
        answer = np.where(degraded, np.maximum(t_k, deadline), full)
    if delays:
        hedged = np.min([fork + dt.type(d) + dup[h]
                         for h, d in enumerate(delays)], axis=0)
        degraded = degraded & (answer <= hedged)
        answer = np.minimum(answer, hedged)
    return answer, degraded


def simulate(draws_of, hedge_of, *, lam, prm: dict, r: int, down=(),
             timeout=None, quorum_k=1, delays=(), n_queries: int,
             quantile_q: float = QUANTILE, dtype=np.float64) -> dict:
    """Mean and quantile response time, the degraded share, and each
    query's fork-to-join time, of one scenario.

    ``draws_of(c)`` gives chunk c's draws of one row (``draws.py``),
    ``hedge_of(c)`` its hedge draws (``hedge_draws``); ``lam`` and every
    ``prm`` value are (1,) arrays.  With nothing down, no timeout and no
    hedge this is ``cluster.simulate`` under round robin, number for
    number.
    """
    dt = np.dtype(dtype)
    f = lambda x: np.asarray(x, np.float64).astype(dt)  # noqa: E731
    n = n_queries
    n_chunks = -(-n // cluster.chunk_size(n))
    d = [draws_of(c) for c in range(n_chunks)]
    cat = {name: np.concatenate([x[name] for x in d], axis=-1)[..., :n]
           for name in ("u_gap", "u_brk", "u_srv")}
    lam_d = f(lam)
    s_server = f(analytic.server_time(prm["hit"], prm["s_hit"],
                                      prm["s_miss"], prm["s_disk"]))
    gaps = f(cat["u_gap"]) / lam_d[:, None]
    arrivals = np.cumsum(gaps, axis=-1)[0]
    broker = (f(cat["u_brk"]) * f(prm["s_broker"])[:, None])[0]
    servers = (f(cat["u_srv"]) * s_server[:, None, None])[0]
    if delays:
        dup = f(np.concatenate([hedge_of(c) for c in range(n_chunks)],
                               axis=-1)[:, :n]) * s_server[0]
    else:
        dup = np.zeros((0, n), dt)
    survivors = [j for j in range(r) if j not in set(down)]
    rep = np.asarray(survivors)[np.arange(n) % len(survivors)]
    response = np.zeros(n, dt)
    fork_to_join = np.zeros(n, dt)
    degraded = np.zeros(n, bool)
    for j in survivors:
        q = np.flatnonzero(rep == j)
        b_done = cluster.fcfs(arrivals[q], broker[q])
        s_done = cluster.fcfs(b_done[None, :], servers[:, q])
        answer, degraded[q] = join(s_done, b_done, dup[:, q],
                                   timeout=timeout, quorum_k=quorum_k,
                                   delays=delays, dtype=dt)
        response[q] = answer - arrivals[q]
        fork_to_join[q] = answer - b_done
    warm = int(n * cluster.WARMUP_FRACTION)
    kept = response[None, warm:]
    count = np.full(1, float(kept.shape[-1]))
    mean = np.add.reduce(kept, axis=-1, dtype=dt) / dt.type(kept.shape[-1])
    log_lo, step = cluster.hist_edges(lam, prm, r, None)
    resp = kept.astype(np.float64)
    bins = np.clip(np.floor((np.log(np.maximum(resp, 1e-30))
                             - log_lo[:, None]) / step),
                   0, cluster.HIST_BINS - 1)
    hist = np.bincount(bins[0].astype(np.int64),
                       minlength=cluster.HIST_BINS)[None, :]
    return {"mean": float(mean[0]),
            "quantile": float(cluster.quantile(hist, count, log_lo, step,
                                               quantile_q)[0]),
            "degraded": float(np.mean(degraded[warm:])),
            "fork_to_join": fork_to_join[warm:].astype(np.float64)}


def grow(faulted_run, slo_s: float, n0: int) -> tuple[int, dict]:
    """The smallest fleet from ``n0`` up whose faulted 95th percentile
    meets the SLO, and its run: fleets n0, n0 + 1, n0 + 2, n0 + 4, ...
    until one holds, then a bisection between the last that missed and
    it.  Where none below ``REPLICA_CAP`` holds, the cap, however it
    reads."""
    runs = {}

    def holds(n):
        runs[n] = faulted_run(n)
        return runs[n]["quantile"] <= slo_s

    if holds(n0):
        return n0, runs[n0]
    lo, step = n0, 1
    while lo < REPLICA_CAP:
        hi = min(n0 + step, REPLICA_CAP)
        if holds(hi):
            while hi - lo > 1:
                mid = (lo + hi) // 2
                if holds(mid):
                    hi = mid
                else:
                    lo = mid
            return hi, runs[hi]
        lo, step = hi, 2 * step
    return lo, runs[lo]


def plan(config: dict, n_queries: int, slo_s: float, key_seed: int,
         rate: float, dtype=np.float64) -> dict:
    """The reference's plan for one N+k what-if call."""
    prm = deployment.scenario_params(config)
    fault = fault_of(config)
    k_down = int(config["survive_faults"])
    out = analytic.plan(prm, rate, slo_s, None, dtype)
    key = draws.key_of(key_seed)
    chunk = cluster.chunk_size(n_queries)

    @functools.lru_cache(maxsize=None)
    def d(c):
        return draws.chunk_draws(
            key, c, [0], n_scen=1, chunk=chunk, p=prm["p"],
            lam32=np.asarray([rate], np.float32),
            prm32=draws.params32(prm))

    @functools.lru_cache(maxsize=None)
    def h(c):
        return hedge_draws(key, c, chunk=chunk, p=prm["p"],
                           attempts=len(fault["delays"]))

    row = {k: np.asarray([v]) if k != "p" else v for k, v in prm.items()}

    def sim(r, down):
        return simulate(d, h, lam=np.asarray([rate]), prm=row, r=r,
                        down=down, n_queries=n_queries, dtype=dtype,
                        **fault)

    n, faulted = grow(lambda n: sim(n, tuple(range(k_down))), slo_s,
                      out["n_replicas"] + k_down)
    whole = sim(n, ())
    out.update(n_replicas=n,
               response_simulated_ms=whole["mean"] * 1e3,
               response_simulated_p95_ms=whole["quantile"] * 1e3,
               response_faulted_p95_ms=faulted["quantile"] * 1e3,
               faulted_degraded_fraction=faulted["degraded"])
    return out
