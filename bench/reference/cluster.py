"""A plain sequential simulation of the replicated fork-join search cluster.

The system of the paper's Fig 8, replicated as in Section 6: a dispatcher
sends each query to one of r replicas; a replica is a broker FCFS queue
followed by p index-server FCFS queues that all serve the query (the fork)
and a join that waits for the slowest.  With a result cache (Eq 8) a query
is a hit with probability hit_r and is then served by its replica's cache
FCFS queue instead of the broker and the servers.

Each queue serves its queries in arrival order and follows the FCFS
recurrence C_i = max(A_i, C_{i-1}) + S_i; times are absolute, from the
first arrival of the run.  Every value is held in ``dtype`` (float64 for
the reference; the control runs the same code in bfloat16).

Routing:

* round robin: query g (counted from 0 over the whole run) goes to g mod r;
* join-shortest-queue: the dispatcher tracks, per replica and server, the
  seconds of work left, drains it by each gap, sends the query to the
  replica whose slowest server frees first (the lowest index on a tie),
  and adds the query's service times there (nothing for a cache hit).
  The choice is discrete, so the tracker is kept in float32, the precision
  in which the planner decides; a float64 tracker would break near-ties
  the other way now and then, and the rest of such a run would follow a
  different route.

Statistics, as the planner defines them by default (its entry points are
called without these settings, so they are the reference's constants):
queries with index in [int(WARMUP_FRACTION * n), n) count; the
response-time histogram has HIST_BINS log bins over six decades, starting three decades below the
Eq 7 upper bound at the per-replica rate lam (1 - hit_r) / r; a quantile is
read from it with log-linear interpolation inside its bin.
"""

from __future__ import annotations

import math

import numpy as np

from . import analytic, draws

HIST_DECADES_BELOW = 3.0
HIST_DECADES_TOTAL = 6.0
HIST_BINS = 256
WARMUP_FRACTION = 0.1


def hist_edges(lam, prm: dict, r: int, cache):
    """(log of the lowest edge, log bin width) per scenario."""
    ref_rate = np.asarray(lam, np.float64)
    if cache is not None:
        ref_rate = ref_rate * (1.0 - cache[0])
    _, hi = analytic.bounds(ref_rate / r, prm)
    s = analytic.server_time(prm["hit"], prm["s_hit"], prm["s_miss"],
                             prm["s_disk"])
    scale = np.where(np.isfinite(hi) & (hi > 0), hi, 100.0 * s)
    log_lo = np.log(scale) - HIST_DECADES_BELOW * math.log(10.0)
    step = HIST_DECADES_TOTAL * math.log(10.0) / HIST_BINS
    return log_lo, step


def quantile(hist, count, log_lo, step, q: float):
    """q-quantile per row from (rows, bins) histogram counts."""
    hist = np.asarray(hist, np.float64)
    n_bins = hist.shape[-1]
    cum = np.cumsum(hist, axis=-1)
    target = q * np.asarray(count, np.float64)
    k = np.clip(np.sum(cum < target[:, None], axis=-1), 0, n_bins - 1)
    rows = np.arange(hist.shape[0])
    before = np.where(k > 0, cum[rows, np.maximum(k - 1, 0)], 0.0)
    frac = np.clip((target - before) / np.maximum(hist[rows, k], 1.0),
                   0.0, 1.0)
    return np.exp(log_lo + (k + frac) * step)


def chunk_size(n_queries: int) -> int:
    """Queries per chunk of a run of ``n_queries``: the planner's chunk,
    or the whole run where it is shorter."""
    return min(draws.CHUNK, int(n_queries))


def fcfs(arrivals, services):
    """Completion times of one FCFS queue, along the last axis.

    C_i = max(A_i, C_{i-1}) + S_i with no work before the first arrival,
    unrolled: C_i = max over j <= i of (A_j + S_j + ... + S_i), which is
    P_i + max over j <= i of (A_j - P_j + S_j) with P the running sum of
    the service times.
    """
    total = np.cumsum(services, axis=-1)
    return total + np.maximum.accumulate(arrivals - total + services,
                                         axis=-1)


def jsq_route(gaps, services, live, r: int):
    """Replica of each query under join-shortest-queue.

    ``gaps`` (n,), ``services`` (n, p) and the tracker share one dtype;
    ``live`` (n,) is False for queries that leave no work (cache hits).
    """
    work = np.zeros((r, services.shape[-1]), services.dtype)
    zero = services.dtype.type(0.0)
    rep = np.zeros(gaps.shape[0], np.int64)
    for q in range(gaps.shape[0]):
        work = np.maximum(work - gaps[q], zero)
        j = int(np.argmin(work.max(axis=-1)))
        rep[q] = j
        if live[q]:
            work[j] = work[j] + services[q]
    return rep


def simulate(draws, *, lam, prm: dict, r: int, routing: str, cache,
             n_queries: int, quantile_q: float, dtype=np.float64) -> dict:
    """Mean and quantile response time of each of k scenarios.

    ``draws(c)`` gives chunk c's draws for the k rows (see ``draws.py``),
    ``chunk_size(n_queries)`` queries each; ``lam`` and every ``prm``
    value are (k,) arrays; ``cache`` is ``(hit_r, s_cache)`` or None.
    """
    dt = np.dtype(dtype)
    f = lambda x: np.asarray(x, np.float64).astype(dt)  # noqa: E731
    n = n_queries
    d = [draws(c) for c in range(-(-n // chunk_size(n)))]
    cat = {name: np.concatenate([x[name] for x in d], axis=-1)[..., :n]
           for name in d[0]}
    lam_d = f(lam)
    k = lam_d.shape[0]
    s_server = f(analytic.server_time(prm["hit"], prm["s_hit"],
                                      prm["s_miss"], prm["s_disk"]))
    gaps = f(cat["u_gap"]) / lam_d[:, None]
    arrivals = np.cumsum(gaps, axis=-1)
    broker = f(cat["u_brk"]) * f(prm["s_broker"])[:, None]
    servers = f(cat["u_srv"]) * s_server[:, None, None]
    hits = (np.asarray(cat["is_hit"], bool) if cache is not None
            else np.zeros((k, n), bool))
    g = np.arange(n)
    response = np.zeros((k, n), dt)
    for i in range(k):
        if r == 1:
            rep = np.zeros(n, np.int64)
        elif routing == "round_robin":
            rep = g % r
        elif routing == "jsq":
            # the planner decides on float32 work; the control on its own
            if dt == np.float64:
                gap_w, svc_w = cat["gap32"][i], cat["svc32"][i].T
            else:
                gap_w, svc_w = gaps[i], servers[i].T
            rep = jsq_route(gap_w, np.ascontiguousarray(svc_w),
                            ~hits[i], r)
        else:
            raise ValueError(f"unknown routing {routing!r}")
        for j in range(r):
            miss = np.flatnonzero((rep == j) & ~hits[i])
            b_done = fcfs(arrivals[i, miss], broker[i, miss])
            s_done = fcfs(b_done[None, :], servers[i][:, miss])
            response[i, miss] = s_done.max(axis=0) - arrivals[i, miss]
            hit = np.flatnonzero((rep == j) & hits[i])
            if cache is not None and hit.size:
                t_cache = f(cat["u_cache"][i, hit]) * dt.type(cache[1])
                response[i, hit] = (fcfs(arrivals[i, hit], t_cache)
                                    - arrivals[i, hit])
    kept = response[:, int(n * WARMUP_FRACTION):]
    count = np.full(k, float(kept.shape[-1]))
    mean = np.add.reduce(kept, axis=-1, dtype=dt) / dt.type(kept.shape[-1])
    log_lo, step = hist_edges(lam, prm, r, cache)
    resp = kept.astype(np.float64)
    bins = np.clip(np.floor((np.log(np.maximum(resp, 1e-30))
                             - log_lo[:, None]) / step), 0, HIST_BINS - 1)
    hist = np.zeros((k, HIST_BINS))
    for i in range(k):
        hist[i] = np.bincount(bins[i].astype(np.int64),
                              minlength=HIST_BINS)
    return {"count": count, "mean": mean.astype(np.float64),
            "quantile": quantile(hist, count, log_lo, step, quantile_q)}
