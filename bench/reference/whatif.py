"""The reference's answer to a what-if call (``plan_capacity``): the
Section 6 sizing and the simulated cross-check of the sized cluster."""

from __future__ import annotations

import numpy as np

from . import analytic, cluster, deployment, draws

# plan_capacity reports the simulated 95th percentile
QUANTILE = 0.95


def plan(config: dict, n_queries: int, slo_s: float, key_seed: int,
         rate: float, dtype=np.float64) -> dict:
    """The reference's plan for one what-if call."""
    prm = deployment.scenario_params(config)
    cache = deployment.result_cache(config)
    out = analytic.plan(prm, rate, slo_s, cache, dtype)
    key = draws.key_of(key_seed)
    hit_r = None if cache is None else cache[0]

    def d(c):
        return draws.chunk_draws(
            key, c, [0], n_scen=1, chunk=cluster.chunk_size(n_queries),
            p=prm["p"], lam32=np.asarray([rate], np.float32),
            prm32=draws.params32(prm), hit_r=hit_r)

    sim = cluster.simulate(
        d, lam=np.asarray([rate]),
        prm={k: np.asarray([v]) if k != "p" else v for k, v in prm.items()},
        r=out["n_replicas"], routing=config["routing"], cache=cache,
        n_queries=n_queries, quantile_q=QUANTILE, dtype=dtype)
    out["response_simulated_ms"] = float(sim["mean"][0]) * 1e3
    out["response_simulated_p95_ms"] = float(sim["quantile"][0]) * 1e3
    return out
