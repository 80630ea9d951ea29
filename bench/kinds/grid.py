"""Planning-grid calls: ``plan_over_grid(grid, slo, simulate=True)``.

A traffic file of this kind gives the grid's rate, cpu, disk, hit and
replica axes, the SLO, the quantile the frontier is drawn on and the
simulated queries per scenario; the configuration gives p, the disk-cache
times of its memory column, the broker, the routing and the result cache.
Each call takes a fresh key; on more than one chip the scenario axis is
sharded over ``make_sweep_mesh()``.  The scan implementation, the chunk
and the statistics are the entry point's own defaults (the reference
restates them in ``reference/``).

The numbers compared with the reference, after the window:

* ``frontier_mismatch``: over every call of the window, the rates at
  which the planner's frontier differs from the reference's cheapest
  feasible cell on the planner's own quantile surface (feasibility, cost
  to 1e-6, or a chosen response over the SLO);
* ``mean_rel``, ``p95_rel``: the largest relative gap between the
  planner's mean (quantile) surface and the reference's, over the calls
  and scenarios sampled from the seed.
"""

from __future__ import annotations

import jax
import numpy as np

from calls import Record, key_seeds
from check import CONTROL_DTYPE, max_rel
from reference import deployment, frontier, grid

# work is scenarios x queries a call: simulated queries per second
FAMILY = "grid"
TRAFFIC = {"lam", "cpu", "disk", "hit", "r", "slo_s", "quantile",
           "n_queries", "check"}
CHECK = {"calls", "scenarios"}
CONFIG = {"p", "table_ms", "broker_fit_ms", "scenario", "routing",
          "result_cache"}
FRONTIER = ("feasible", "cost", "p", "cpu", "disk", "hit", "r", "response")


def fr_arrays(fr) -> dict:
    """A frontier's arrays by name."""
    return {k: getattr(fr, k) for k in FRONTIER}


def frontier_mismatch(config: dict, traffic: dict, answer: dict) -> int:
    """Rates at which the planner's frontier is not the cheapest feasible
    cell of its own quantile surface."""
    costs = frontier.cell_costs([config["p"]], traffic["cpu"],
                                traffic["disk"], traffic["hit"],
                                traffic["r"])
    slo = float(traffic["slo_s"])
    ref = frontier.frontier(answer["quantile"], costs, slo)
    got = answer["frontier"]
    bad = np.asarray(got["feasible"], bool) != ref["feasible"]
    both = ref["feasible"] & ~bad
    cost_ok = np.isclose(np.asarray(got["cost"], np.float64), ref["cost"],
                         rtol=1e-6, atol=0.0)
    resp_ok = np.asarray(got["response"], np.float64) <= slo
    bad |= both & ~(cost_ok & resp_ok)
    return int(bad.sum())


class Calls:
    def __init__(self, config: dict, traffic: dict, chips: int, seed: int):
        from repro.core import planner, sweep
        from repro.core.cluster import ClusterSpec
        from repro.core.queueing import ServerParams
        self.planner = planner
        self.config, self.traffic, self.chips = config, traffic, chips
        base = deployment.grid_params(config, config["scenario"]["memory"],
                                      1.0, 1.0, 0.0)
        self.grid = sweep.SweepGrid.build(
            lam=list(grid.lam_axis(traffic["lam"])), p=[float(config["p"])],
            cpu=list(traffic["cpu"]), disk=list(traffic["disk"]),
            hit=list(traffic["hit"]), r=list(traffic["r"]),
            base=ServerParams(p=config["p"],
                              s_broker=float(base["s_broker"]),
                              s_hit=base["s_hit"], s_miss=base["s_miss"],
                              s_disk=base["s_disk"], hit=0.0),
            broker_from_p=False)
        self.cluster = ClusterSpec(routing=config["routing"],
                                   result_cache=deployment.result_cache(
                                       config))
        self.mesh = None
        if chips > 1:
            from repro.launch.mesh import make_sweep_mesh
            self.mesh = make_sweep_mesh()
        self.work = self.grid.n_scenarios * int(traffic["n_queries"])
        self._seeds = key_seeds(seed)

    def _run(self, key_seed: int):
        res, fr = self.planner.plan_over_grid(
            self.grid, float(self.traffic["slo_s"]), simulate=True,
            key=jax.random.PRNGKey(key_seed),
            quantile=float(self.traffic["quantile"]),
            n_queries=int(self.traffic["n_queries"]), cluster=self.cluster,
            mesh=self.mesh)
        fr = fr_arrays(fr)
        jax.block_until_ready((res.stats, fr))
        return res, fr

    def warm(self) -> None:
        self._run(0)

    def call(self) -> Record:
        rec = Record(key_seed=next(self._seeds))
        rec.answer = self._run(rec.key_seed)
        return rec

    def failed(self, rec: Record) -> bool:
        """Whether the call left a scenario without a simulated mean."""
        return not bool(np.isfinite(np.asarray(rec.answer[0].mean)).all())

    def host_answer(self, rec: Record) -> dict:
        """The call's surfaces and frontier as host arrays."""
        res, fr = rec.answer
        q = float(self.traffic["quantile"])
        n_l = self.grid.shape[0]
        return {"mean": np.asarray(res.mean, np.float64).reshape(n_l, -1),
                "quantile": np.asarray(res.quantile(q),
                                       np.float64).reshape(n_l, -1),
                "frontier": {k: np.asarray(v) for k, v in fr.items()}}

    def sample(self, n_calls: int, seed: int):
        """(calls, scenarios of each) to compare, drawn from the seed."""
        rng = np.random.default_rng([int(seed), 2])
        chk = self.traffic["check"]
        n_cells = self.grid.n_scenarios
        calls = np.sort(rng.choice(n_calls, min(chk["calls"], n_calls),
                                   replace=False))
        scen = np.sort(rng.choice(n_cells, min(chk["scenarios"], n_cells),
                                  replace=False))
        return calls, scen

    def numbers(self, records, seed: int, latencies=None,
                control: bool = False) -> dict:
        """The numbers compared; ``control=True`` puts the bfloat16
        reference in the planner's place (and skips the frontier, which
        it does not compute)."""
        config, traffic, chips = self.config, self.traffic, self.chips
        numbers = {}
        if not control:
            answers = [self.host_answer(rec) for rec in records]
            numbers["frontier_mismatch"] = sum(
                frontier_mismatch(config, traffic, a) for a in answers)
        which, scen = self.sample(len(records), seed)
        mean_rel = p95_rel = 0.0
        for i in which:
            key_seed = records[i].key_seed
            ref = grid.simulate(config, traffic, chips, key_seed, scen)
            if control:
                got = grid.simulate(config, traffic, chips, key_seed, scen,
                                    CONTROL_DTYPE)
            else:
                got = {k: answers[i][k].reshape(-1)[scen]
                       for k in ("mean", "quantile")}
            mean_rel = max(mean_rel, max_rel(got["mean"], ref["mean"]))
            p95_rel = max(p95_rel, max_rel(got["quantile"],
                                           ref["quantile"]))
        numbers["mean_rel"] = mean_rel
        numbers["p95_rel"] = p95_rel
        return numbers
