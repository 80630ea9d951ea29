#!/usr/bin/env python3
"""Run the capacity planner's main path once on a TPU and check the answers.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the scenario-sharded sweeps, 4 chips

One chip runs, in one process, through the public entry points:

* what-if: ``plan_capacity(simulate=True)`` for the paper's Section 6
  headline (200 qps at a 300 ms SLO on the memory+cpus+disks cluster,
  p = 100), whose analytic answer is 4 replicas x 100 servers and whose
  simulated cross-check runs that fleet on the chip;
* planning grid: ``plan_over_grid(simulate=True, quantile=0.95)`` over
  1,024 scenarios per (p, r) dispatch, at p = 100 and r in {1, 2}, once
  with the Pallas kernel and once with ``associative_scan``;
* kernel: the (max, +) scan kernels on one real-width chunk (1,024
  scenarios x 100 servers rows of 4,096 queries) against the sequential
  recurrence of ``kernels/maxplus_scan/ref.py``.

``--chips 4`` runs only the scenario-sharded ``sweep_analytical`` and
``sweep_simulated`` over a 4-chip mesh and their unsharded references.

Every phase checks its results and raises on a mismatch.  Times printed
on the way are single chip measurements, not a benchmark.  The last line
of standard output is ``{"ok": true, "device": {...}}``; it is printed
only after every check passed.  Without a TPU the script exits non-zero
before doing any work.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import capacity, planner, simulator, sweep  # noqa: E402
from repro.core.arrivals import ArrivalProcess  # noqa: E402
from repro.core.cluster import ClusterSpec  # noqa: E402
from repro.core.queueing import ServerParams  # noqa: E402
from repro.kernels.maxplus_scan import ops as mp_ops  # noqa: E402
from repro.kernels.maxplus_scan import ref as mp_ref  # noqa: E402
from repro.launch.mesh import make_sweep_mesh  # noqa: E402

SLO = 0.300            # seconds (paper Section 6)
TARGET_QPS = 200.0     # paper Section 6: 4 x 100 servers

# Pallas against associative_scan: both run the same (max, +) recurrence
# on the same draws and differ only in the order of float32 max/add.  The
# engine rebases clocks to each chunk, so a completion time carries a few
# ulps of one chunk's span; averaged over tens of thousands of queries
# the mean and the log-interpolated p95 move far less than 1e-3, while a
# lost carry or a missed segment moves them by whole percents (one
# histogram bin is 2.7%).
SURFACE_RTOL = 1e-3
# Kernel against the sequential recurrence: the blocked doubling scan
# reassociates at most log2(4096) = 12 float32 additions per output.
KERNEL_RTOL = 1e-5
# A sharded shard against its direct rebuild: the same program on the
# same draws, so only a different fusion could move a sum.
SHARD_RTOL = 1e-5
# The sharded analytic surfaces against the unsharded ones: the same
# elementwise Eq 7/8 math on the same inputs, split across chips.
ANALYTIC_RTOL = 0.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Problem sizes of the smoke; FULL is what the chip runs."""

    whatif_queries: int = 60_000           # plan_capacity's default
    p: int = 100                           # Table 6 servers per replica
    lam: tuple = tuple(np.linspace(10.0, 160.0, 16).tolist())
    cpu: tuple = (1.0, 2.0, 3.0, 4.0)      # Section 6 upgrade factors
    disk: tuple = (1.0, 2.0, 3.0, 4.0)
    hit: tuple = (0.02, 0.09, 0.15, 0.18)  # Table 6 disk-cache hits
    grid_queries: int = 50_000
    chunk: int = 4096
    kernel_rows: int = 1024 * 100          # scenarios x servers
    kernel_check_rows: int = 8
    analytic_lam: int = 100                # 1,000,000-scenario grid
    analytic_hit: int = 20


FULL = Sizes()


def check(ok, what: str) -> None:
    if not bool(ok):
        raise RuntimeError(f"check failed: {what}")


def timed(fn):
    """(seconds, result) of fn() with every output array on the host."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return time.perf_counter() - t0, out


def measured_on() -> str:
    d = jax.devices()[0]
    return f"measured on {d.platform} {d.device_kind}, not a benchmark"


def peak_bytes() -> str:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [x for x in peaks if x is not None]
    return f"{max(peaks):,} B" if peaks else "not reported by this backend"


def max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    m = np.isfinite(b)
    check((np.isfinite(a) == m).all(), "finite masks agree")
    if not m.any():
        return 0.0
    return float(np.max(np.abs(a[m] - b[m]) / np.maximum(np.abs(b[m]),
                                                          1e-30)))


def require_tpu(n_chips: int):
    devices = jax.devices()
    print(f"jax.devices(): {devices}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform!r} "
                         "devices; this smoke runs only on the chip")
    if len(devices) != n_chips:
        raise SystemExit(f"expected {n_chips} TPU chips, JAX found "
                         f"{len(devices)}")
    return devices


def check_kernel_compiled(hlo_text: str) -> None:
    check("tpu_custom_call" in hlo_text,
          "the impl='pallas' stream program holds the compiled kernel "
          "(tpu_custom_call)")


# ------------------------------------------------------------- one chip
def phase_whatif(sizes: Sizes, key) -> None:
    params = capacity.scenario("memory+cpus+disks")
    run = functools.partial(capacity.plan_capacity, params, TARGET_QPS,
                            SLO, simulate=True, key=key,
                            n_queries=sizes.whatif_queries)
    cold, plan = timed(run)
    warm, again = timed(run)
    check(plan.n_replicas == 4 and plan.servers_per_replica == 100,
          f"the paper's 4 x 100 plan (got {plan.n_replicas} x "
          f"{plan.servers_per_replica})")
    check(plan.response_simulated_ms is not None
          and np.isfinite(plan.response_simulated_ms)
          and np.isfinite(plan.response_simulated_p95_ms),
          "the simulated cross-check ran and is finite")
    check(again == plan, "a repeated plan with the same key is identical")
    qps = sizes.whatif_queries / warm
    print(f"what-if: {plan.n_replicas} replicas x "
          f"{plan.servers_per_replica} servers for {TARGET_QPS:g} qps at "
          f"{SLO * 1e3:g} ms; analytic bounds "
          f"[{plan.response_lower_ms:.1f}, {plan.response_upper_ms:.1f}] "
          f"ms; simulated mean {plan.response_simulated_ms:.2f} ms, p95 "
          f"{plan.response_simulated_p95_ms:.2f} ms")
    print(f"what-if timing ({measured_on()}): first call {cold:.3f} s "
          f"(compile + run), warm {warm:.3f} s, compile ~{cold - warm:.3f}"
          f" s, {qps:,.0f} simulated queries/s")


def _grid(sizes: Sizes, r) -> sweep.SweepGrid:
    return sweep.SweepGrid.build(lam=list(sizes.lam), cpu=list(sizes.cpu),
                                 disk=list(sizes.disk), hit=list(sizes.hit),
                                 p=[float(sizes.p)], r=r, memory=4)


def check_frontier(res, frontier, grid: sweep.SweepGrid) -> int:
    """The frontier against a plain numpy argmin over the p95 surface."""
    surf = np.asarray(res.quantile(0.95), np.float64).reshape(
        grid.shape[0], -1)
    p, cpu, disk, hit, r = np.meshgrid(
        np.asarray(grid.p), np.asarray(grid.cpu), np.asarray(grid.disk),
        np.asarray(grid.hit), np.asarray(grid.r), indexing="ij")
    cost = (p * (1.0 + 0.5 * (cpu - 1.0) + 0.25 * (disk - 1.0) + hit)
            * r).reshape(-1)
    ok = surf <= SLO
    feasible = ok.any(axis=1)
    check((np.asarray(frontier.feasible) == feasible).all(),
          "the frontier is feasible exactly where some cell meets the SLO")
    for i in np.flatnonzero(feasible):
        best = np.min(np.where(ok[i], cost, np.inf))
        check(np.isclose(float(frontier.cost[i]), best, rtol=1e-6),
              f"rate {grid.lam[i]}: the frontier's cost is the cheapest "
              "feasible cell")
        check(float(frontier.response[i]) <= SLO,
              f"rate {grid.lam[i]}: the chosen cell meets the SLO")
    return int(feasible.sum())


def _blockable(out):
    """plan_over_grid's (result, frontier) as arrays jax can wait on."""
    res, frontier = out
    jax.block_until_ready((res.stats, frontier.cost, frontier.feasible))
    return res, frontier


def phase_grid(sizes: Sizes, key) -> None:
    grid = _grid(sizes, [1.0, 2.0])
    n_slab = grid.n_scenarios // grid.shape[5]
    surfaces = {}
    for impl in ("pallas", "xla"):
        run = functools.partial(
            planner.plan_over_grid, grid, SLO, simulate=True, key=key,
            quantile=0.95, n_queries=sizes.grid_queries,
            chunk_size=sizes.chunk, impl=impl)
        cold, _ = timed(lambda: _blockable(run()))
        warm, (res, frontier) = timed(lambda: _blockable(run()))
        n_feasible = check_frontier(res, frontier, grid)
        surfaces[impl] = (np.asarray(res.mean),
                          np.asarray(res.quantile(0.95)))
        qps = grid.n_scenarios * sizes.grid_queries / warm
        print(f"grid impl={impl}: {grid.n_scenarios} scenarios "
              f"({n_slab} per (p, r) dispatch, p={sizes.p}, r in {{1, 2}}),"
              f" {sizes.grid_queries} queries each; frontier feasible at "
              f"{n_feasible}/{grid.shape[0]} rates")
        print(f"grid impl={impl} timing ({measured_on()}): first call "
              f"{cold:.3f} s (compile + run), warm {warm:.3f} s, compile "
              f"~{cold - warm:.3f} s, {qps:,.0f} simulated queries/s")
        for i in range(grid.shape[0]):
            print(f"  {frontier.describe(i)}")
    d_mean = max_rel(surfaces["pallas"][0], surfaces["xla"][0])
    d_p95 = max_rel(surfaces["pallas"][1], surfaces["xla"][1])
    print(f"pallas vs xla: max relative difference mean {d_mean:.3e}, p95 "
          f"{d_p95:.3e} (tolerance {SURFACE_RTOL:g})")
    check(d_mean <= SURFACE_RTOL and d_p95 <= SURFACE_RTOL,
          "the Pallas and XLA surfaces agree")

    # the impl="pallas" stream program, lowered with the dispatch's
    # statics and shapes, must carry the kernel as a Mosaic custom call
    vec = jax.ShapeDtypeStruct((n_slab,), jnp.float32)
    lowered = jax.jit(functools.partial(
        simulator.simulate_fork_join_batch, n_queries=sizes.grid_queries,
        p=sizes.p, impl="pallas", chunk_size=sizes.chunk,
        cluster=ClusterSpec(r=2))).lower(
            key, vec, ServerParams(*(vec,) * 6))
    check_kernel_compiled(lowered.as_text())
    print("impl=pallas stream program: tpu_custom_call present")
    print(f"peak device memory after the grid: {peak_bytes()}")


def phase_kernel(sizes: Sizes, key) -> None:
    rows, n = sizes.kernel_rows, sizes.chunk

    @jax.jit
    def chunk_inputs(k):
        k1, k2, k3 = jax.random.split(k, 3)
        arrivals = jnp.cumsum(jax.random.exponential(k1, (rows, n)) / 50.0,
                              axis=-1)
        services = jax.random.exponential(k2, (rows, n)) * 0.03
        flags = jax.random.uniform(k3, (rows, n)) < 1.0 / 64.0
        return arrivals + services, services, flags.at[:, 0].set(True)

    a, b, f = chunk_inputs(key)
    pick = np.unique(np.linspace(0, rows - 1, sizes.kernel_check_rows)
                     .astype(int))
    cases = (("maxplus_scan", mp_ops.maxplus_scan, (a, b),
              mp_ref.maxplus_scan_sequential),
             ("maxplus_segment_scan", mp_ops.maxplus_segment_scan,
              (a, b, f), mp_ref.maxplus_segment_scan_sequential))
    for name, kernel, args, seq in cases:
        cold, _ = timed(lambda: kernel(*args))
        warm, (out_a, out_b) = timed(lambda: kernel(*args))
        ref_a, ref_b = seq(*(x[pick] for x in args))
        da = max_rel(out_a[pick], ref_a)
        db = max_rel(out_b[pick], ref_b)
        del out_a, out_b
        print(f"kernel {name} on ({rows}, {n}) f32: rows {pick.tolist()} "
              f"vs the sequential recurrence, max relative difference "
              f"{da:.2e} / {db:.2e} (tolerance {KERNEL_RTOL:g})")
        print(f"kernel {name} timing ({measured_on()}): first call "
              f"{cold:.3f} s, warm {warm:.4f} s, "
              f"{rows * n / warm / 1e9:.2f} G elements/s")
        check(da <= KERNEL_RTOL and db <= KERNEL_RTOL,
              f"{name} equals the sequential recurrence")
    print(f"peak device memory after the kernels: {peak_bytes()}")


def run_one_chip(sizes: Sizes, key) -> None:
    k_whatif, k_grid, k_kernel = jax.random.split(key, 3)
    phase_whatif(sizes, k_whatif)
    phase_grid(sizes, k_grid)
    phase_kernel(sizes, k_kernel)


# ----------------------------------------------------------- four chips
def check_sharded(x, devices, what: str) -> None:
    """Every device holds its own shard of ``x``."""
    held = {s.device for s in x.addressable_shards}
    check(x.sharding.device_set == set(devices) and held == set(devices)
          and not x.sharding.is_fully_replicated,
          f"{what} is split over all {len(devices)} devices")


def phase_sharded_analytic(sizes: Sizes, mesh, devices) -> None:
    # the 100 x 4 x 5 x 5 x 20 x 5 grid of benchmarks/sharded_bench.py
    big = sweep.SweepGrid.build(
        lam=jnp.linspace(10.0, 120.0, sizes.analytic_lam),
        p=jnp.asarray([50.0, 100.0, 200.0, 400.0]),
        cpu=jnp.linspace(1.0, 3.0, 5),
        disk=jnp.linspace(1.0, 3.0, 5),
        hit=jnp.linspace(0.05, 0.95, sizes.analytic_hit),
        r=jnp.asarray([1.0, 2.0, 4.0, 8.0, 16.0]),
        base=capacity.TABLE5_PARAMS,
        result_cache=(0.2, 2e-3))
    surfaces = ("response_lower", "response_upper", "utilization")

    def run(mesh_or_none):
        res = sweep.sweep_analytical(big, mesh=mesh_or_none)
        return tuple(getattr(res, s) for s in surfaces)

    cold, _ = timed(lambda: run(mesh))
    warm, sharded = timed(lambda: run(mesh))
    _, local = timed(lambda: run(None))
    for name, a, b in zip(surfaces, sharded, local):
        check_sharded(a, devices, f"sharded {name}")
        a, b = np.asarray(a), np.asarray(b)
        rel = max_rel(a, b)
        n_diff = int(np.sum((a != b) & np.isfinite(b)))
        print(f"sharded analytic {name}: {n_diff} of {b.size:,} values "
              f"differ from the unsharded surface, max relative "
              f"difference {rel:.3e} (tolerance {ANALYTIC_RTOL:g})")
        check(rel <= ANALYTIC_RTOL,
              f"sharded {name} equals the unsharded surface")
    print(f"sharded analytic: {big.n_scenarios:,} scenarios on "
          f"{len(devices)} chips equal the unsharded surfaces")
    print(f"sharded analytic timing ({measured_on()}): first call "
          f"{cold:.3f} s, warm {warm:.4f} s, "
          f"{big.n_scenarios / warm:,.0f} scenarios/s")


def phase_sharded_simulated(sizes: Sizes, key, mesh, devices) -> None:
    grid = _grid(sizes, [2.0])
    n_dev = len(devices)
    run = functools.partial(
        sweep.sweep_simulated, grid, key, n_queries=sizes.grid_queries,
        chunk_size=sizes.chunk, impl="pallas", mesh=mesh)
    cold, _ = timed(lambda: run().stats)
    warm, stats = timed(lambda: run().stats)
    check_sharded(stats.sum_response, devices, "the simulated surface")

    # rebuild every device's shard with a direct batch run under that
    # device's split key, each on its own device: one (p, r) dispatch, so
    # the dispatch key is split(key, 1)[0]; slab scenarios flatten
    # (L, C, D, H) row-major and the mesh cuts them into equal blocks
    n_slab = grid.n_scenarios
    check(n_slab % n_dev == 0, "the slab splits evenly (no padding)")
    per = n_slab // n_dev
    lam_full, params_full = grid.broadcast_full()
    lam_slab = lam_full.reshape(-1)
    params_slab = jax.tree_util.tree_map(lambda x: x.reshape(-1),
                                         params_full)
    dev_keys = jax.random.split(jax.random.split(key, 1)[0], n_dev)
    direct = []
    for d, dev in enumerate(devices):
        blk = slice(d * per, (d + 1) * per)
        k, lam_d, par_d = jax.device_put(
            (dev_keys[d], lam_slab[blk],
             jax.tree_util.tree_map(lambda x: x[blk], params_slab)), dev)
        direct.append(simulator.simulate_fork_join_batch(
            k, ArrivalProcess.stationary(lam_d), par_d, sizes.grid_queries,
            p=sizes.p, impl="pallas", chunk_size=sizes.chunk,
            cluster=ClusterSpec(r=2)))
    direct = jax.block_until_ready(direct)
    got_count = np.asarray(stats.count).reshape(-1)
    got_sum = np.asarray(stats.sum_response).reshape(-1)
    worst = 0.0
    for d, res in enumerate(direct):
        blk = slice(d * per, (d + 1) * per)
        check(next(iter(res.sum_response.devices())) == devices[d],
              f"the direct rebuild of shard {d} ran on device {d}")
        check((got_count[blk] == np.asarray(res.count)).all(),
              f"shard {d}: query counts equal the direct rebuild")
        rel = max_rel(got_sum[blk], res.sum_response)
        worst = max(worst, rel)
        check(rel <= SHARD_RTOL,
              f"shard {d}: response sums equal the direct rebuild "
              f"(max relative difference {rel:.2e})")
    print(f"sharded simulated: {n_slab} scenarios (p={sizes.p}, r=2), "
          f"{per} per chip; every shard matches its direct rebuild on its "
          f"own chip, max relative difference {worst:.2e} (tolerance "
          f"{SHARD_RTOL:g})")
    print(f"sharded simulated timing ({measured_on()}): first call "
          f"{cold:.3f} s, second call {warm:.3f} s, "
          f"{n_slab * sizes.grid_queries / warm:,.0f} simulated queries/s")
    print(f"peak device memory: {peak_bytes()}")


def run_four_chips(sizes: Sizes, key) -> None:
    devices = jax.devices()
    mesh = make_sweep_mesh()
    check(mesh.devices.size == len(devices), "the mesh spans every device")
    phase_sharded_analytic(sizes, mesh, devices)
    phase_sharded_simulated(sizes, key, mesh, devices)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: what-if, planning grid and kernels; 4: the "
                         "scenario-sharded sweeps only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    enable_compile_cache()
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 4:
        run_four_chips(FULL, key)
    else:
        run_one_chip(FULL, key)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
