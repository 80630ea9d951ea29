"""Benchmark harness: one function per paper table/figure + kernel/DES
micro-benches.  Prints ``name,us_per_call,derived`` CSV.

Usage: PYTHONPATH=src python -m benchmarks.run [--only SUBSTR[,SUBSTR...]]

``--only`` takes a comma-separated list of substrings; a benchmark runs
if ANY of them occurs in its function name (so CI's regression job can
ask for ``--only streaming,calibrate,replicated`` in one pass).
Environment knobs for CI live in `benchmarks._util`: ``BENCH_QUICK=1``
shrinks horizons, ``BENCH_OUTPUT_DIR`` redirects the BENCH_*.json
records.
"""

from __future__ import annotations

import argparse
import sys
import traceback

from repro.compile_cache import enable_compile_cache

from benchmarks import (calibrate_bench, faults_bench, kernels_bench,
                        obs_bench, paper_tables, partitioning_bench,
                        replicated_bench, sharded_bench, streaming_bench,
                        sweep_bench)

BENCHES = [
    paper_tables.bench_table2_query_lengths,
    paper_tables.bench_fig2_zipf_popularity,
    paper_tables.bench_table3_folding,
    paper_tables.bench_fig6_interarrival_fits,
    paper_tables.bench_fig7_service_time_fits,
    paper_tables.bench_fig9_server_residence,
    paper_tables.bench_fig10_response_vs_lambda,
    paper_tables.bench_fig11_response_vs_p,
    paper_tables.bench_fig12_scenarios,
    paper_tables.bench_fig13_upgrade_grids,
    paper_tables.bench_fig14_result_cache,
    paper_tables.bench_table5_measurement,
    kernels_bench.bench_maxplus_scan,
    kernels_bench.bench_flash_attention,
    kernels_bench.bench_decode_attention,
    kernels_bench.bench_embedding_bag,
    kernels_bench.bench_cin_fuse,
    kernels_bench.bench_simulator_scale,
    sweep_bench.bench_sweep_grid,
    sweep_bench.bench_sweep_simulated,
    streaming_bench.bench_streaming_sweep,
    replicated_bench.bench_replicated_sweep,
    faults_bench.bench_faults,
    sharded_bench.bench_sharded_sweep,
    calibrate_bench.bench_calibrate,
    obs_bench.bench_obs_telemetry,
    partitioning_bench.bench_partitioning,
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated name substrings to run")
    args = ap.parse_args()
    wanted = ([s.strip() for s in args.only.split(",") if s.strip()]
              if args.only else None)

    enable_compile_cache()
    rows = []
    failures = 0
    for bench in BENCHES:
        if wanted and not any(w in bench.__name__ for w in wanted):
            continue
        try:
            bench(rows)
        except Exception:  # noqa: BLE001 — keep the harness running
            failures += 1
            print(f"# BENCH FAILED: {bench.__name__}", file=sys.stderr)
            traceback.print_exc()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
