"""Scenario-sharded sweep benchmark: the million-scenario planning path.

Measures `sweep_analytical`/`sweep_simulated` with a 1-D ("scenario",)
mesh from `repro.launch.mesh.make_sweep_mesh` over every device this
process sees (one chip, the four chips of a host, or the one CPU device),
and records how many in ``n_devices``:

* analytical — a 1,000,000-scenario (L,P,C,D,H,R) grid evaluated as one
  shard_map program (the SNIPPETS.md 38M-qps global planning exercise
  needs surfaces of this size);
* simulated — a replicated fused-engine grid streamed with each device
  owning a scenario shard.

It runs in the harness's own process: a chip belongs to one process, so
a child could not reach it.  The sharded path on virtual CPU devices is
rehearsed by ``tests/test_sharding.py``.  Results go to
``BENCH_sharded.json`` for the bench-regression gate (``queries_per_s``
and ``scenarios_per_s`` are both gated "higher").
"""

from __future__ import annotations

import json
import statistics
import time

from benchmarks import _util

_TIMING_PASSES = 3


def bench_sharded_sweep(rows):
    import jax
    import jax.numpy as jnp

    from repro.core import capacity, sweep
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh()
    n_dev = int(mesh.devices.size)

    # --- analytical: 100 x 4 x 5 x 5 x 20 x 5 = 1,000,000 scenarios ----
    big = sweep.SweepGrid.build(
        lam=jnp.linspace(10.0, 120.0, 100),
        p=jnp.asarray([50.0, 100.0, 200.0, 400.0]),
        cpu=jnp.linspace(1.0, 3.0, 5),
        disk=jnp.linspace(1.0, 3.0, 5),
        hit=jnp.linspace(0.05, 0.95, 20),
        r=jnp.asarray([1.0, 2.0, 4.0, 8.0, 16.0]),
        base=capacity.TABLE5_PARAMS,
        result_cache=(0.2, 2e-3),
    )
    n_ana = big.n_scenarios

    def run_ana():
        res = sweep.sweep_analytical(big, mesh=mesh)
        jax.block_until_ready(res.response_upper)
        return res

    run_ana()                                   # compile + warm
    times = []
    for _ in range(_TIMING_PASSES):
        t0 = time.perf_counter()
        run_ana()
        times.append(time.perf_counter() - t0)
    dt_ana = statistics.median(times)

    # --- simulated: 32-scenario replicated slab, sharded over the mesh --
    sim_grid = sweep.SweepGrid.build(
        lam=jnp.linspace(30.0, 90.0, 16),
        p=jnp.asarray([8.0]),
        hit=jnp.asarray([0.17, 0.5]),
        r=jnp.asarray([2.0]),
        base=capacity.TABLE5_PARAMS,
        broker_from_p=False,
        result_cache=(0.2, 2e-3),
    )
    n_sim = sim_grid.n_scenarios
    # quick stays large: the sharded path pays ~5s of per-call trace/
    # dispatch overhead, and a small horizon would sink queries_per_s
    # far below the full-size baseline the regression gate compares to
    n_q = _util.scale_queries(200_000, 150_000)

    def run_sim():
        res = sweep.sweep_simulated(
            sim_grid, jax.random.PRNGKey(0), n_queries=n_q,
            chunk_size=4096, mesh=mesh)
        jax.block_until_ready(res.mean)
        return res

    run_sim()                                   # compile + warm
    times = []
    for _ in range(_TIMING_PASSES):
        t0 = time.perf_counter()
        run_sim()
        times.append(time.perf_counter() - t0)
    dt_sim = statistics.median(times)

    # SweepResult carries the grid (not a pytree); profile the surfaces
    def _surfaces():
        res = sweep.sweep_analytical(big, mesh=mesh)
        return {"response_lower": res.response_lower,
                "response_upper": res.response_upper,
                "utilization": res.utilization}

    profile = _util.profile_block(
        jax.jit(_surfaces),
        name=f"sharded_analytical[{n_ana}x{n_dev}dev]", n_runs=0)

    record = {
        "bench": "sharded_sweep",
        "n_devices": n_dev,
        "n_scenarios_analytical": n_ana,
        "wall_seconds_analytical": dt_ana,
        "scenarios_per_s": n_ana / dt_ana,
        "n_scenarios_simulated": n_sim,
        "n_queries": n_q,
        "chunk_size": 4096,
        "r": 2,
        "routing": "round_robin",
        "wall_seconds": dt_sim,
        "queries_per_s": n_sim * n_q / dt_sim,
        "profile": profile,
    }
    out = _util.bench_output_path("BENCH_sharded.json")
    out.write_text(json.dumps(record, indent=2) + "\n")
    rows.append((
        "sharded_sweep", dt_sim * 1e6,
        f"{n_ana} analytic scenarios on {n_dev} devices, "
        f"{n_ana / dt_ana / 1e6:.2f}M scen/s; simulated {n_sim} scen x "
        f"{n_q} q sharded: {n_sim * n_q / dt_sim / 1e6:.2f}M queries/s; "
        f"-> {out}"))
