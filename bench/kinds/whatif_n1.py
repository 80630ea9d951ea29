"""N+k what-if calls on a tail-tolerant cluster:
``plan_capacity(params, rate, slo, simulate=True, survive_faults=k)`` with
the configuration's broker timeout, partial quorum and hedging.

A traffic file of this kind reads as the ``whatif`` kind's, whose calls
these extend: the set of peak rates, the SLO and the simulated queries per
call, the rates in blocks, each a permutation of the whole set drawn from
the seed.  The configuration adds ``survive_faults`` and a ``fault`` group
(the broker timeout and quorum, the hedge delay and attempts), whose keys
this module checks itself.  Each call simulates the N+k fleet with
replicas 0..k-1 down (growing it while the faulted p95 misses the SLO) and
then the returned fleet with every replica up, both through the quorum
join and the hedge draws.

The numbers compared with the reference (``reference/tail.py``), over the
calls sampled from the seed and the slowest call of the window:

* ``sizing_mismatch``: calls whose returned replica count differs;
* ``analytic_rel``: the largest relative gap of the per-replica rate, the
  Eq 7 bounds and the utilization;
* ``mean_rel``, ``p95_rel``: the largest relative gap of the simulated
  mean (95th percentile) of the returned fleet, every replica up;
* ``faulted_p95_rel``: the largest relative gap of the 95th percentile
  with k replicas down;
* ``degraded_abs``: the largest absolute gap of the share of the faulted
  run's queries answered on a partial quorum.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from calls import kind_module
from check import CONTROL_DTYPE, max_rel
from reference import deployment, tail

whatif = kind_module("whatif")

# the window's calls are whole planning calls, one answer each
FAMILY = "whatif"
TRAFFIC, CHECK, ANALYTIC = whatif.TRAFFIC, whatif.CHECK, whatif.ANALYTIC
CONFIG = whatif.CONFIG | {"survive_faults", "fault"}
FAULT = {"broker_timeout_ms", "quorum_k", "hedge_after_ms",
         "hedge_attempts"}
PLAN = whatif.PLAN + ("response_faulted_p95_ms", "faulted_degraded_fraction")


def check_config(config: dict) -> None:
    """Refuse a ``fault`` group with a key missing or unread, and a
    deployment the reference does not model."""
    where = f"configuration {config.get('name')!r}"
    deployment.check_keys(config["fault"], FAULT, f"{where} fault", FAULT)
    if config["routing"] != "round_robin":
        raise ValueError(f"{where}: the N+k reference models round-robin "
                         f"failover only; routing {config['routing']!r}")
    if config["result_cache"] is not None:
        raise ValueError(f"{where}: the N+k reference models no result "
                         "cache")
    if int(config["survive_faults"]) < 1:
        raise ValueError(f"{where}: survive_faults must be at least 1")


class Calls(whatif.Calls):
    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        from repro.core.faults import FaultSpec
        check_config(config)
        super().__init__(config, traffic, chips, seed)
        f = config["fault"]
        self.cluster = dataclasses.replace(self.cluster, fault=FaultSpec(
            broker_timeout_seconds=f["broker_timeout_ms"] * deployment.MS,
            quorum_k=int(f["quorum_k"]),
            hedge_after_seconds=f["hedge_after_ms"] * deployment.MS,
            hedge_attempts=int(f["hedge_attempts"])))
        self.k_down = int(config["survive_faults"])

    def _run(self, rate: float, key_seed: int):
        """One call; the warm-up's call at each rate compiles the faulted
        program at the rate's N+k size (and any size its loop grows to)
        and the fault-free one at the returned size."""
        return self.capacity.plan_capacity(
            self.params, rate, float(self.traffic["slo_s"]),
            cluster=self.cluster, simulate=True,
            key=jax.random.PRNGKey(key_seed), n_queries=self.work,
            survive_faults=self.k_down)

    def failed(self, rec) -> bool:
        """Whether the plan came without either simulated check."""
        a = rec.answer
        return any(v is None or not np.isfinite(v) for v in (
            a.response_simulated_ms, a.response_faulted_p95_ms,
            a.faulted_degraded_fraction))

    def host_answer(self, rec) -> dict:
        return {f: getattr(rec.answer, f) for f in PLAN}

    def numbers(self, records, seed: int, latencies,
                control: bool = False) -> dict:
        """The numbers compared; ``control=True`` puts the reference,
        computed in bfloat16, in the planner's place."""
        args = (self.config, self.work, float(self.traffic["slo_s"]))
        numbers = {"sizing_mismatch": 0, "analytic_rel": 0.0,
                   "mean_rel": 0.0, "p95_rel": 0.0, "faulted_p95_rel": 0.0,
                   "degraded_abs": 0.0}
        for i in self.sample(latencies, seed):
            rec = records[i]
            ref = tail.plan(*args, rec.key_seed, rec.rate)
            if control:
                got = tail.plan(*args, rec.key_seed, rec.rate, CONTROL_DTYPE)
            else:
                got = self.host_answer(rec)
            numbers["sizing_mismatch"] += int(got["n_replicas"]
                                              != ref["n_replicas"])
            numbers["analytic_rel"] = max(numbers["analytic_rel"], max_rel(
                [got[k] for k in ANALYTIC], [ref[k] for k in ANALYTIC]))
            for name, field in (("mean_rel", "response_simulated_ms"),
                                ("p95_rel", "response_simulated_p95_ms"),
                                ("faulted_p95_rel",
                                 "response_faulted_p95_ms")):
                numbers[name] = max(numbers[name], max_rel(
                    got[field] if got[field] is not None else np.nan,
                    ref[field]))
            got_d = got["faulted_degraded_fraction"]
            gap = (np.nan if got_d is None
                   else abs(got_d - ref["faulted_degraded_fraction"]))
            # np.maximum keeps a nan, which the judgement then fails
            numbers["degraded_abs"] = float(np.maximum(
                numbers["degraded_abs"], gap))
        return numbers
