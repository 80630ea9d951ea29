"""The paper's analytic model (Badue et al. 2010, Sections 5 and 6) in float64.

Written from the paper's equations, independently of the planner:

* Eq 1   S_server = hit * S_hit + (1 - hit) * (S_miss + S_disk)
* Eq 2/4 M/M/1 residence R = S / (1 - lambda S), infinite at saturation
* Eq 6   fork-join upper bound H_p * R_server
* Eq 7   R_server + R_broker <= R <= H_p R_server + R_broker
* Eq 8   result cache: R_8 = R_7,upper (1 - hit_r) + R_cache hit_r, with the
         cache queue at the full (not thinned) rate
* Section 6 sizing: the largest per-replica rate whose upper bound meets
  the SLO, and ceil(target / that rate) replicas.

Every value is held in ``dtype``: float64 for the reference, bfloat16 for
the control.
"""

from __future__ import annotations

import math

import numpy as np


def _in(dtype):
    dt = np.dtype(dtype)
    return dt, (lambda x: np.asarray(x, np.float64).astype(dt))


def harmonic(p: int, dtype=np.float64):
    """H_p = 1 + 1/2 + ... + 1/p."""
    dt, f = _in(dtype)
    total = dt.type(0.0)
    for k in range(1, int(p) + 1):
        total = total + dt.type(1.0) / f(k)
    return total


def server_time(hit, s_hit, s_miss, s_disk, dtype=np.float64):
    """Eq 1."""
    _, f = _in(dtype)
    hit = f(hit)
    return hit * f(s_hit) + (f(1.0) - hit) * (f(s_miss) + f(s_disk))


def mm1(lam, s, dtype=np.float64):
    """Eq 2/4; +inf at and past saturation."""
    _, f = _in(dtype)
    lam, s = f(lam), f(s)
    rho = lam * s
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(rho < f(1.0), s / (f(1.0) - rho), f(np.inf))


def bounds(lam, prm: dict, dtype=np.float64):
    """Eq 7: (lower, upper) response-time bounds at rate ``lam``."""
    s = server_time(prm["hit"], prm["s_hit"], prm["s_miss"], prm["s_disk"],
                    dtype)
    r_server = mm1(lam, s, dtype)
    r_broker = mm1(lam, prm["s_broker"], dtype)
    return (r_server + r_broker,
            harmonic(prm["p"], dtype) * r_server + r_broker)


def upper(lam, prm: dict, cache=None, dtype=np.float64):
    """Eq 7's upper bound, or Eq 8's with ``cache=(hit_r, s_cache)``."""
    _, f = _in(dtype)
    _, hi = bounds(lam, prm, dtype)
    if cache is None:
        return hi
    hit_r, s_cache = f(cache[0]), cache[1]
    return hi * (f(1.0) - hit_r) + mm1(lam, s_cache, dtype) * hit_r


def max_rate_under_slo(prm: dict, slo: float, cache=None, iters: int = 200,
                       dtype=np.float64):
    """Largest per-replica rate whose upper bound meets ``slo`` (0 if none).

    The bound rises monotonically up to saturation, so bisection on
    [0, saturation) converges to the root.
    """
    _, f = _in(dtype)
    s = server_time(prm["hit"], prm["s_hit"], prm["s_miss"], prm["s_disk"],
                    dtype)
    sat = np.minimum(f(1.0) / s, f(1.0) / f(prm["s_broker"]))
    if upper(1e-6, prm, cache, dtype) > f(slo):
        return f(0.0)
    lo, hi = f(0.0), sat * f(1.0 - 1e-6)
    for _ in range(iters):
        mid = f(0.5) * (lo + hi)
        if upper(mid, prm, cache, dtype) <= f(slo):
            lo = mid
        else:
            hi = mid
    return lo


def plan(prm: dict, target: float, slo: float, cache=None,
         dtype=np.float64) -> dict:
    """Section 6's answer: replicas, per-replica rate, bounds, utilization."""
    _, f = _in(dtype)
    per_replica = max_rate_under_slo(prm, slo, cache, dtype=dtype)
    n = math.ceil(float(f(target) / np.maximum(per_replica, f(1e-9))))
    rate = f(target) / f(max(n, 1))
    lo, _ = bounds(rate, prm, dtype)
    hi = upper(rate, prm, cache, dtype)
    s = server_time(prm["hit"], prm["s_hit"], prm["s_miss"], prm["s_disk"],
                    dtype)
    return {"n_replicas": n, "per_replica_rate_qps": float(rate),
            "response_upper_ms": float(hi) * 1e3,
            "response_lower_ms": float(lo) * 1e3,
            "utilization": float(rate * s)}
