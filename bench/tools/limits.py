#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/tools/limits.py --workload t6.whatif --seeds 12 --calls 12

For each seed the cell's calls are set up once and then issued as a short
closed-loop window of ``--calls`` calls at the cell's own sizes; the
window's answers are compared with the reference exactly as a benchmark
run compares them (the program's readings), and the same sample is worked
again by the control, the reference in bfloat16 (the control's readings).
Prints one JSON line per seed and a last line with, per number, the
largest program reading and the smallest control reading.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run as bench_run  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--calls", type=int, default=12)
    args = ap.parse_args(argv)
    bench = bench_run.load_json(bench_run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    bench_run.require_chip(int(cell["chips"]))
    bench_run.enable_cache()
    import calls as calls_mod
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = bench_run.load_json(bench_run.ROOT / conf["file"])
    traffic = bench_run.load_json(
        bench_run.BENCH / "traffic" / f"{cell['traffic']}.json")
    worst, least = {}, {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        calls = calls_mod.make(config, traffic, cell["chips"], seed)
        if i == 0:
            calls.warm()
        records, lat = [], []
        for _ in range(args.calls):
            t0 = time.perf_counter()
            records.append(calls.call())
            lat.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        prog = calls.numbers(records, seed, lat)
        t_ref = time.perf_counter() - t0
        ctrl = calls.numbers(records, seed, lat, control=True)
        for k, v in prog.items():
            worst[k] = max(worst.get(k, 0.0), v)
        for k, v in ctrl.items():
            least[k] = min(least.get(k, float("inf")), v)
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          "call_s": sum(lat) / len(lat),
                          "reference_s": t_ref}), flush=True)
    print(json.dumps({"workload": cell["name"], "lower": worst,
                      "control_least": least}), flush=True)


if __name__ == "__main__":
    main()
