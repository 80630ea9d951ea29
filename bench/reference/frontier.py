"""The planner's frontier: per rate, the cheapest configuration that meets
the SLO (paper Section 6 at grid scale).

A replica of p servers costs p * (1 + 0.5 (cpu - 1) + 0.25 (disk - 1) +
hit), and r replicas r times that.  A rate with no cell at or under the SLO
is infeasible.
"""

from __future__ import annotations

import numpy as np


def cell_costs(p, cpu, disk, hit, r):
    """Cost of every (p, cpu, disk, hit, r) cell, flattened in that order."""
    gp, gc, gd, gh, gr = np.meshgrid(
        np.asarray(p, np.float64), np.asarray(cpu, np.float64),
        np.asarray(disk, np.float64), np.asarray(hit, np.float64),
        np.asarray(r, np.float64), indexing="ij")
    return (gp * (1.0 + 0.5 * (gc - 1.0) + 0.25 * (gd - 1.0) + gh)
            * gr).reshape(-1)


def frontier(surface, costs, slo: float) -> dict:
    """Per rate (the surface's first axis): feasible, cost, index, response.

    ``surface`` is (L, cells) in the order of :func:`cell_costs`; the
    cheapest feasible cell wins, the first one on a tie.
    """
    surface = np.asarray(surface, np.float64).reshape(len(surface), -1)
    ok = surface <= slo
    masked = np.where(ok, costs[None, :], np.inf)
    best = np.argmin(masked, axis=1)
    rows = np.arange(len(surface))
    return {"feasible": ok.any(axis=1), "cost": masked[rows, best],
            "index": best, "response": surface[rows, best]}
