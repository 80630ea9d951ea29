"""What-if calls: ``plan_capacity(params, rate, slo, simulate=True)``.

A traffic file of this kind gives the set of peak rates, the SLO and the
simulated queries per call; the configuration gives its scenario (the
cluster a planner asks about), the routing and the result cache.  Each
call takes a fresh key and a rate from the set.  The rates come in
blocks, each a permutation of the whole set drawn from the seed, so every
seed asks for the same work in another order.

The numbers compared with the reference, over the calls sampled from the
seed and the slowest call of the window:

* ``sizing_mismatch``: calls whose replica count differs;
* ``analytic_rel``: the largest relative gap of the per-replica rate, the
  Eq 7/8 bounds and the utilization;
* ``mean_rel``, ``p95_rel``: the largest relative gap of the simulated
  mean (95th percentile) of the sized cluster.
"""

from __future__ import annotations

import jax
import numpy as np

from calls import Record, key_seeds
from check import CONTROL_DTYPE, max_rel
from reference import deployment, whatif

# the window's calls are whole planning calls, one answer each
FAMILY = "whatif"
TRAFFIC = {"rates", "slo_s", "n_queries", "check"}
CHECK = {"calls"}
CONFIG = {"p", "table_ms", "broker_fit_ms", "scenario", "routing",
          "result_cache"}
ANALYTIC = ("per_replica_rate_qps", "response_upper_ms",
            "response_lower_ms", "utilization")
PLAN = ("n_replicas",) + ANALYTIC + ("response_simulated_ms",
                                     "response_simulated_p95_ms")


class Calls:
    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        from repro.core import capacity
        from repro.core.cluster import ClusterSpec
        from repro.core.queueing import ServerParams
        self.capacity = capacity
        self.config, self.traffic, self.chips = config, traffic, chips
        self.params = ServerParams(**deployment.scenario_params(config))
        self.cluster = ClusterSpec(routing=config["routing"],
                                   result_cache=deployment.result_cache(
                                       config))
        self.rates = [float(x) for x in traffic["rates"]]
        self.work = int(traffic["n_queries"])
        self._seeds = key_seeds(seed)
        self._order = np.random.default_rng([int(seed), 1])
        self._queue = []

    def _run(self, rate: float, key_seed: int):
        return self.capacity.plan_capacity(
            self.params, rate, float(self.traffic["slo_s"]),
            cluster=self.cluster, simulate=True,
            key=jax.random.PRNGKey(key_seed), n_queries=self.work)

    def warm(self) -> None:
        for rate in self.rates:
            self._run(rate, 0)

    def call(self) -> Record:
        if not self._queue:
            self._queue = [self.rates[i]
                           for i in self._order.permutation(len(self.rates))]
        rec = Record(key_seed=next(self._seeds), rate=self._queue.pop(0))
        rec.answer = self._run(rec.rate, rec.key_seed)
        return rec

    def failed(self, rec: Record) -> bool:
        """Whether the plan came without its simulated cross-check."""
        sim = rec.answer.response_simulated_ms
        return sim is None or not np.isfinite(sim)

    def host_answer(self, rec: Record) -> dict:
        return {f: getattr(rec.answer, f) for f in PLAN}

    def sample(self, latencies, seed: int) -> list:
        """Calls to compare: a sample drawn from the seed, and the slowest."""
        rng = np.random.default_rng([int(seed), 2])
        n = len(latencies)
        pick = set(rng.choice(n, min(self.traffic["check"]["calls"], n),
                              replace=False).tolist())
        pick.add(int(np.argmax(latencies)))
        return sorted(pick)

    def numbers(self, records, seed: int, latencies,
                control: bool = False) -> dict:
        """The numbers compared; ``control=True`` puts the reference,
        computed in bfloat16, in the planner's place."""
        args = (self.config, self.work, float(self.traffic["slo_s"]))
        numbers = {"sizing_mismatch": 0, "analytic_rel": 0.0,
                   "mean_rel": 0.0, "p95_rel": 0.0}
        for i in self.sample(latencies, seed):
            rec = records[i]
            ref = whatif.plan(*args, rec.key_seed, rec.rate)
            if control:
                got = whatif.plan(*args, rec.key_seed, rec.rate,
                                  CONTROL_DTYPE)
            else:
                got = self.host_answer(rec)
            numbers["sizing_mismatch"] += int(got["n_replicas"]
                                              != ref["n_replicas"])
            numbers["analytic_rel"] = max(numbers["analytic_rel"], max_rel(
                [got[k] for k in ANALYTIC], [ref[k] for k in ANALYTIC]))
            for name, field in (("mean_rel", "response_simulated_ms"),
                                ("p95_rel", "response_simulated_p95_ms")):
                numbers[name] = max(numbers[name], max_rel(
                    got[field] if got[field] is not None else np.nan,
                    ref[field]))
        return numbers
